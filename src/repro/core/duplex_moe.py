"""Dual-path MoE execution — expert co-processing (paper §V-B) on TPU.

The paper splits each MoE layer's experts between xPU (experts serving many
tokens) and Logic-PIM (experts serving few), chosen by the greedy makespan
partitioner over latency LUTs. On a TPU both "paths" share the chip, but the
split still wins in roofline terms (DESIGN.md §2):

  * hot experts  -> the *grouped-GEMM* path: capacity-padded (E_hot, C_hot, d)
    buffers with MXU-aligned C_hot — compute-dense, weights read once;
  * cold experts -> the *gather-GEMV* path (kernels/moe_gemv.py): a small
    (k_cold, C_cold, d) buffer with C_cold sized for the tail. With the
    baseline single-capacity dispatch, a 64-expert layer at decode batch 128
    pads every expert to the same capacity C — the top-1 expert's token count
    — so the padded-FLOP waste is O(E·C_max·d·f). Splitting removes it.

jit constraint: shapes must be static, so the *cold count* ``k_cold`` and the
two capacities are compile-time constants chosen by the host-side planner
(`core/partition.py`, one-stage-stale router statistics). The *membership*
(which experts are hot) is dynamic: experts are ranked by token count inside
the jitted function. The Pallas kernels read the expert weights in place:
they take the table row of each rank (``perm``, offset by ``layer * E`` in
a segment's flattened (L*E, ., .) layer stack) as a scalar-prefetch
operand, so no gathered, sliced, padded or per-layer copy of a table is
made. The XLA path gathers one layer's tables into rank order.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.core.execution import EXPERT_TABLES, note_moe_lowering
from repro.models.ffn import ffn_apply
from repro.models.moe import RouterOut, route
from repro.sharding.rules import logical_constraint


def _align(x: int, a: int) -> int:
    return max(a, -(-x // a) * a)


def default_capacities(T: int, m: MoEConfig, k_cold: int,
                       n_shards: int = 1) -> Tuple[int, int]:
    """(C_hot, C_cold) per dispatch shard of T tokens. Hot capacity covers
    skewed routing (factor on the uniform expectation); cold capacity covers
    the tail experts only. MXU alignment (128) applies to the *merged*
    (n_shards × C) token dim, so per-shard capacity aligns to 128/n."""
    mean = T * m.top_k / m.num_experts
    a_hot = max(8 // max(n_shards, 1), 4)
    a_cold = max(8 // max(n_shards, 1), 2)
    # hot capacity covers routing skew (~mean + 3 sigma of a multinomial);
    # cold capacity covers the tail experts only. MXU padding to 128 is the
    # kernel's own BlockSpec concern, NOT baked into the slot buffers.
    sigma = (mean * (1.0 - m.top_k / m.num_experts)) ** 0.5
    c_hot = _align(int(mean + 3.0 * sigma) + 1, a_hot)
    # Cold experts are the k_cold *least-loaded* ranks, so their capacity is
    # governed by the count at the cold/hot boundary rank — the normal-order-
    # statistic expectation mean + sigma·Φ⁻¹(k_cold/E) — not the uniform
    # mean, plus a fluctuation margin (the realized boundary count wobbles
    # stage to stage; without slack the largest cold expert would overflow
    # and drop tokens on a large fraction of stages). For small cold sets
    # (the common planner outcome) the boundary quantile is deep in the
    # lower tail, so C_cold shrinks well below the mean; at k_cold = E it
    # recovers the worst expert (≈ hot capacity).
    if k_cold > 0:
        from statistics import NormalDist
        q = min(max(k_cold / m.num_experts, 1e-6), 1.0 - 1e-6)
        z = NormalDist().inv_cdf(q)
        boundary = mean + z * sigma + max(mean, 0.0) ** 0.5
    else:
        boundary = mean
    c_cold = _align(int(max(boundary, 0.0)) + 1, a_cold)
    return c_hot, c_cold


class DuplexDispatch(NamedTuple):
    src_token: jnp.ndarray      # (n, n_slots) per-shard token per slot (Tl=none)
    slot_gate: jnp.ndarray      # (n, n_slots) fp32
    perm: jnp.ndarray           # (E,) expert id per rank (ascending count)
    counts: jnp.ndarray         # (E,) tokens per expert
    k_cold: int
    c_hot: int                  # per-shard hot capacity
    c_cold: int                 # per-shard cold capacity


def duplex_dispatch(router: RouterOut, m: MoEConfig, T: int, *, k_cold: int,
                    n_shards: int = 1, c_hot: Optional[int] = None,
                    c_cold: Optional[int] = None,
                    token_valid=None) -> DuplexDispatch:
    """Rank experts by token count; build per-shard slot buffers where rank
    r < k_cold gets C_cold slots (GEMV path) and the rest get C_hot slots
    (GEMM path). Capacities are per shard (hierarchical dispatch).
    ``token_valid`` (T,) masks padded serving rows out of slot assignment
    (router.counts must have been computed with the same mask so the ragged
    kernels' live counts match the dispatched slot prefixes)."""
    from repro.models.moe import group_positions, shard_dispatch
    E, k = m.num_experts, m.top_k
    n = n_shards
    Tl = T // n
    if c_hot is None or c_cold is None:
        ch, cc = default_capacities(Tl, m, k_cold, n)
        c_hot = c_hot or ch
        c_cold = c_cold or cc
    if k_cold == 0:
        c_cold = 0
    n_slots = k_cold * c_cold + (E - k_cold) * c_hot

    counts = router.counts                                    # (E,) global
    perm = jnp.argsort(counts, stable=True).astype(jnp.int32)  # rank -> expert
    rank = jnp.zeros((E,), jnp.int32).at[perm].set(
        jnp.arange(E, dtype=jnp.int32))                        # expert -> rank

    # per-expert slot base + capacity in RANK order (cold ranks first)
    ranks = jnp.arange(E, dtype=jnp.int32)
    is_cold_rank = ranks < k_cold
    base_of_rank = jnp.where(is_cold_rank, ranks * c_cold,
                             k_cold * c_cold + (ranks - k_cold) * c_hot)
    cap_of_rank = jnp.where(is_cold_rank, c_cold, c_hot)
    caps = cap_of_rank[rank]                                   # per expert
    bases = base_of_rank[rank]

    fe = router.expert_idx.reshape(n, Tl * k)
    fg = router.gates.reshape(n, Tl * k)
    if token_valid is not None:
        fv = jnp.repeat(token_valid.reshape(n, Tl), k, axis=1)
        src, slot_gate = jax.vmap(
            lambda e, g, v: shard_dispatch(e, g, Tl, E, caps, bases, n_slots,
                                           valid=v))(fe, fg, fv)
    else:
        src, slot_gate = jax.vmap(
            lambda e, g: shard_dispatch(e, g, Tl, E, caps, bases,
                                        n_slots))(fe, fg)
    return DuplexDispatch(src, slot_gate, perm, counts,
                          k_cold, c_hot, c_cold)


def _gather_weights(params, perm, layer=None):
    """One layer's expert weights permuted into rank order (one gather: the
    XLA path, and the kernels where d_ff needs a pad). With ``layer`` the
    tables are a segment's (L, E, ., .) stacks."""
    keys = [k for k in EXPERT_TABLES if k in params]
    return {k: jnp.take(params[k] if layer is None else params[k][layer],
                        perm, axis=0) for k in keys}


def _expert_rows(params, perm, layer, in_place: bool):
    """(tables, ids): the weight tables the expert kernels read and the
    table row of each rank. In place, the parameters themselves (a layer
    stack flattened to (L*E, ., .), a bitcast) with ids ``layer*E + perm``;
    else one layer's tables gathered into rank order, with ids the ranks."""
    E = perm.shape[0]
    if not in_place:
        return (_gather_weights(params, perm, layer),
                jnp.arange(E, dtype=jnp.int32))
    keys = [k for k in EXPERT_TABLES if k in params]
    if layer is None:
        return {k: params[k] for k in keys}, perm
    return ({k: params[k].reshape((-1,) + params[k].shape[2:])
             for k in keys}, layer * E + perm)


def _expert_ffn(w, x):
    """x: (e, ..., d) grouped tokens; w leaves (e, d, f)/(e, f, d)."""
    if "wi" in w:                # non-gated experts
        h = jnp.einsum("e...d,edf->e...f", x, w["wi"])
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        return jnp.einsum("e...f,efd->e...d", h, w["wo"])
    g = jnp.einsum("e...d,edf->e...f", x, w["wi_gate"])
    u = jnp.einsum("e...d,edf->e...f", x, w["wi_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("e...f,efd->e...d", h, w["wo"])


def duplex_moe_apply(params, cfg: ModelConfig, x, *, k_cold: int,
                     c_hot: Optional[int] = None, c_cold: Optional[int] = None,
                     use_kernels: bool = False, ragged: bool = False,
                     c_block: int = 256, return_stats: bool = False,
                     token_valid=None, layer=None):
    """Duplex MoE layer: hot experts through the grouped-GEMM path, cold
    experts through the gather-GEMV path. ``k_cold`` is static (planner).

    Semantics match ``models/moe.py::moe_apply`` for sufficient capacities
    (tokens over capacity are dropped, standard capacity-MoE behaviour).
    Dispatch is hierarchical (per batch shard) like the grouped path.

    With ``ragged`` (and ``use_kernels``), per-expert live token counts are
    threaded into the scalar-prefetch kernels: the hot grouped GEMM elides
    dead token-block DMAs/compute and the cold GEMV skips fully empty
    experts, so executed FLOPs and streamed weight bytes scale with the
    routed tokens instead of the capacity padding. Requires a single
    dispatch shard (per-shard slot buffers interleave live slots in the
    merged token dim); multi-shard dispatch falls back to the padded
    kernels.

    With ``layer`` (traced int32) the expert tables in ``params`` are the
    segment's whole (L, E, ., .) stacks. The kernels read them in place
    wherever a kernel tile divides d_ff; otherwise (and on the XLA path)
    the layer's tables are gathered into rank order first.
    """
    from repro.core.execution import shard_blocks
    from repro.models.moe import combine_slots, gather_slots
    m = cfg.moe
    shape = x.shape
    x3 = x if x.ndim == 3 else x[None]
    xb, restore = shard_blocks(x3)                          # (n, Tl, d)
    n, Tl, _ = xb.shape
    T = n * Tl
    x_flat = xb.reshape(T, shape[-1])
    router = route(params, m, x_flat, valid=token_valid)
    disp = duplex_dispatch(router, m, T, k_cold=k_cold, n_shards=n,
                           c_hot=c_hot, c_cold=c_cold,
                           token_valid=token_valid)
    E = m.num_experts
    n_cold = disp.k_cold * disp.c_cold          # per-shard cold slots

    x_slots = gather_slots(xb, disp.src_token)              # (n, n_slots, d)
    in_place = False
    if use_kernels:
        from repro.kernels.ops import expert_f_block
        in_place = expert_f_block(m.d_ff_expert) is not None
    tables, ids = _expert_rows(params, disp.perm, layer, in_place)
    note_moe_lowering("inplace" if in_place else "gathered",
                      1 if layer is None else params["wo"].shape[0])
    # live tokens per slot-buffer expert (rank order; dispatch fills each
    # expert's slots as a contiguous prefix) — scalar-prefetch operands of
    # the ragged kernels. Only exact for a single dispatch shard.
    use_ragged = ragged and use_kernels and n == 1
    counts_rank = disp.counts[disp.perm] if use_ragged else None

    # ---- cold path: (k_cold, n*C_cold, d) — bandwidth-streaming GEMV --------
    if disp.k_cold > 0:
        x_cold = x_slots[:, :n_cold].reshape(n, disp.k_cold, disp.c_cold, -1)
        x_cold = x_cold.transpose(1, 0, 2, 3)   # (k_cold, n, Cc, d)
        if use_kernels:
            from repro.kernels.ops import moe_gemv
            cold_counts = (jnp.minimum(counts_rank[:disp.k_cold], disp.c_cold)
                           if use_ragged else None)
            y_cold = moe_gemv(tables, x_cold.reshape(disp.k_cold,
                                                     n * disp.c_cold, -1),
                              cold_counts, ids=ids[:disp.k_cold])
            y_cold = y_cold.reshape(disp.k_cold, n, disp.c_cold, -1)
        else:
            y_cold = _expert_ffn({k: v[:disp.k_cold]
                                  for k, v in tables.items()}, x_cold)
        y_cold = y_cold.transpose(1, 0, 2, 3).reshape(n, n_cold, -1)
    else:
        y_cold = jnp.zeros((n, 0, shape[-1]), x_flat.dtype)

    # ---- hot path: (E - k_cold, n, C_hot, d) — MXU grouped GEMM -------------
    if disp.k_cold < E:
        x_hot = x_slots[:, n_cold:].reshape(n, E - disp.k_cold, disp.c_hot, -1)
        x_hot = x_hot.transpose(1, 0, 2, 3)
        x_hot = logical_constraint(x_hot,
                                   ("act_exp", "act_cap", None, "act_embed"))
        if use_ragged:
            from repro.kernels.ops import ragged_moe_gemm
            hot_counts = jnp.minimum(counts_rank[disp.k_cold:], disp.c_hot)
            y_hot = ragged_moe_gemm(tables,
                                    x_hot.reshape(E - disp.k_cold,
                                                  n * disp.c_hot, -1),
                                    hot_counts, ids=ids[disp.k_cold:],
                                    c_block=c_block)
            y_hot = y_hot.reshape(E - disp.k_cold, n, disp.c_hot, -1)
        elif use_kernels:
            from repro.kernels.ops import moe_gemm
            y_hot = moe_gemm(tables, x_hot.reshape(E - disp.k_cold,
                                                   n * disp.c_hot, -1),
                             ids=ids[disp.k_cold:], c_block=c_block)
            y_hot = y_hot.reshape(E - disp.k_cold, n, disp.c_hot, -1)
        else:
            y_hot = _expert_ffn({k: v[disp.k_cold:]
                                 for k, v in tables.items()}, x_hot)
        y_hot = logical_constraint(y_hot,
                                   ("act_exp", "act_cap", None, "act_embed"))
        y_hot = y_hot.transpose(1, 0, 2, 3).reshape(
            n, (E - disp.k_cold) * disp.c_hot, -1)
    else:
        y_hot = jnp.zeros((n, 0, shape[-1]), x_flat.dtype)

    y_slots = jnp.concatenate([y_cold.astype(x_flat.dtype),
                               y_hot.astype(x_flat.dtype)], axis=1)
    y_slots = y_slots * disp.slot_gate[..., None].astype(y_slots.dtype)
    y_flat = combine_slots(y_slots, disp.src_token, Tl)
    if m.num_shared_experts:
        y_flat = y_flat + ffn_apply(params["shared"], x_flat)
    y = restore(y_flat).reshape(shape)
    if return_stats:
        return y, router
    return y, router.aux_loss


def moe_traffic_model(counts, *, k_cold: int, c_hot: int, c_cold: int,
                      d_model: int, d_ff: int, c_block: int = 256,
                      itemsize: int = 2, mats: int = 3) -> dict:
    """Modeled per-MoE-layer HBM bytes + FLOPs under the capacity-padded vs
    ragged kernels for one stage's per-expert token counts (host-side; the
    serving engine feeds it the same stage statistics that drive ``k_cold``).

    Hot path: grouped GEMM — padded runs every (expert, token-block) and
    re-streams the expert's ``mats`` weight matrices per block; ragged runs
    live blocks only (``kernels/moe_gemm.py::moe_gemm_traffic`` semantics).
    Cold path: gather GEMV — weights stream once per cold expert (padded)
    vs once per *occupied* cold expert (ragged); FLOPs cover the C_cold slab.
    Returns ``{padded,ragged}_{bytes,weight_bytes,flops}``.
    """
    import numpy as np
    from repro.kernels.moe_gemm import moe_gemm_traffic
    counts = np.sort(np.asarray(counts, dtype=np.int64))   # rank order
    cold, hot = counts[:k_cold], counts[k_cold:]
    out = {k: 0 for k in ("padded_weight_bytes", "ragged_weight_bytes",
                          "padded_bytes", "ragged_bytes",
                          "padded_flops", "ragged_flops")}
    if len(hot) and c_hot > 0:
        t = moe_gemm_traffic(hot, capacity=c_hot, d_model=d_model,
                             d_ff=d_ff, c_block=c_block, itemsize=itemsize,
                             mats=mats)
        for k in out:
            out[k] += t[k]
    if len(cold) and c_cold > 0:
        w_once = mats * d_model * d_ff * itemsize
        a_slab = 2 * c_cold * d_model * itemsize
        flops_slab = 2 * mats * c_cold * d_model * d_ff
        occupied = int((np.minimum(cold, c_cold) > 0).sum())
        out["padded_weight_bytes"] += len(cold) * w_once
        out["ragged_weight_bytes"] += occupied * w_once
        out["padded_bytes"] += len(cold) * (w_once + a_slab)
        out["ragged_bytes"] += occupied * (w_once + a_slab)
        out["padded_flops"] += len(cold) * flops_slab
        out["ragged_flops"] += occupied * flops_slab
    return out


def padded_flops_saved(T: int, m: MoEConfig, k_cold: int, d_model: int,
                       counts=None) -> float:
    """Analytic estimate of the padding-FLOP reduction vs the single-capacity
    grouped path (used by EXPERIMENTS.md §Perf napkin math)."""
    import numpy as np
    if counts is None:
        counts = np.full(m.num_experts, T * m.top_k / m.num_experts)
    counts = np.asarray(counts, dtype=np.float64)
    c_single = _align(int(T * m.top_k * m.capacity_factor / m.num_experts) + 1, 8)
    c_hot, c_cold = default_capacities(T, m, k_cold)
    base = m.num_experts * c_single
    order = np.sort(counts)
    duplex_slots = k_cold * c_cold + (m.num_experts - k_cold) * c_hot
    per_slot = 6.0 * d_model * m.d_ff_expert
    return (base - duplex_slots) * per_slot
