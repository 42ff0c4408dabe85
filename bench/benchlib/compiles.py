"""Programs compiled in a phase, and the Pallas kernels a served program
holds (both after ``chip_smoke.py``)."""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

import jax

KERNEL_NAME = re.compile(r'kernel_name = "(\w+)"')


class CompileCounter:
    """Programs XLA compiled, programs loaded from the persistent cache, and
    the seconds compiling took, from JAX's own monitoring events."""

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.programs, self.seconds, self.cache_hits


class StageCalls:
    """The engine's stage-step call, wrapped. The first call of each jitted
    stage program records its argument shapes, for lowering it again after
    the run. While ``neighbours`` is on, a program whose neighbours are not
    warm yet first runs them once with the same arguments, outputs
    dropped (the step does not donate its inputs): the same stage at the
    planner's ``k_cold`` buckets next to its own and, for a mixed stage,
    the decode-only stage of its decode rows. A window in which the
    planner moves ``k_cold`` by a bucket, or no chunk is due, then compiles
    nothing."""

    def __init__(self, eng):
        self.eng = eng
        self.shapes: Dict = {}
        self.neighbours = False
        self._warmed = set()
        self._invoke = eng._invoke
        eng._invoke = self

    def __call__(self, fn, *args):
        if fn not in self.shapes:
            self.shapes[fn] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        if self.neighbours and fn not in self._warmed:
            self._warmed.add(fn)
            for twin, twin_args in neighbour_programs(self.eng, fn, args):
                if twin not in self._warmed:
                    self._warmed.add(twin)
                    jax.block_until_ready(self._invoke(twin, *twin_args))
        return self._invoke(fn, *args)


def neighbour_programs(eng, fn, args) -> List[Tuple]:
    """(program, arguments) of the neighbours of the paged stage program
    ``fn`` called with ``args`` (see ``StageCalls``)."""
    if eng.planner is None:
        return []
    buckets = eng.planner.buckets
    mixed = {f: k for k, f in eng._mixed_fns.items()}
    decode = {f: k for k, f in eng._paged_decode_fns.items()}
    out = []
    if fn in mixed:
        k, _, _, nc, sc, nb, mp, mpc, spec = mixed[fn]
        i = buckets.index(k)
        for kk in buckets[max(i - 1, 0):i + 2]:
            if kk != k:
                out.append((eng._mixed_fn(
                    kk, *eng._moe_caps(nb + nc * sc, kk), nc, sc, nb, mp,
                    mpc, spec), args))
        # (params, dec_tokens, dec_lengths, dec_bt, ..., cache, key) ->
        # the decode-only step's (params, tokens, cache, lengths, bt, key)
        params, dtok, dlen, dbt = args[:4]
        cache, key = args[-2:]
        for kk in buckets[max(i - 1, 0):i + 2]:
            out.append((eng._paged_decode_fn(kk, *eng._moe_caps(nb, kk), nb,
                                             mp),
                        (params, dtok, cache, dlen, dbt, key)))
    elif fn in decode:
        k, _, _, nb, mp = decode[fn]
        i = buckets.index(k)
        for kk in buckets[max(i - 1, 0):i + 2]:
            if kk != k:
                out.append((eng._paged_decode_fn(kk, *eng._moe_caps(nb, kk),
                                                 nb, mp), args))
    return out


def served_kernels(eng, calls: Dict) -> Dict[int, List[str]]:
    """The Pallas kernels in served mixed-stage programs, one for each kind
    of ``k_cold`` served: {k_cold: kernel names in its lowered text}."""
    progs = {}
    for key, fn in eng._mixed_fns.items():
        if fn in calls:
            progs.setdefault(key[0], fn)
    # one program of each kind: no expert cold, some cold, all cold
    kinds = {}
    for k_cold in sorted(progs):
        kinds.setdefault((k_cold > 0, k_cold == eng.cfg.moe.num_experts),
                         k_cold)
    progs = {k: progs[k] for k in kinds.values()}
    return {k_cold: sorted(set(KERNEL_NAME.findall(
                fn.lower(*calls[fn]).as_text())))
            for k_cold, fn in sorted(progs.items())}


def missing_kernels(kernels: Dict[int, List[str]], num_experts: int,
                    attn: str, hot: str, cold: str) -> Dict[int, List[str]]:
    """Per served ``k_cold``, the kernels it should hold and lacks: the
    paged attention kernel always, the hot GEMM unless every expert went
    cold, the cold GEMV when any did."""
    out = {}
    for k_cold, names in kernels.items():
        want = ({attn} | ({hot} if k_cold < num_experts else set())
                | ({cold} if k_cold > 0 else set()))
        lack = sorted(want - set(names))
        if lack:
            out[k_cold] = lack
    return out
