"""Weights made from the seed by the benchmark itself, and the one place
that knows the layout the program takes them in.

Every matrix is drawn in its own stream, keyed by the seed, the matrix's
name and its layer, so one layer can be drawn again alone (the reference
does that, layer by layer) and gives the same bf16 values that the program
was served. Projections are normal with standard deviation fan_in ** -0.5,
the embedding unit normal, norm scales ones (their published
initialisation).

Canonical names, per layer (x @ W orientation):
    q (d, H*hd)  k, v (d, KV*hd)  o (H*hd, d)
    router (d, E)  gate, up (E, d, f)  down (E, f, d)          MoE layers
    s_gate, s_up (d, fs)  s_down (fs, d)                       shared experts
    d_gate, d_up (d, F)  d_down (F, d)                         dense layers
and once: embed (V, d), lm_head (V, d).
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from benchlib.model import Dims

BF16 = jnp.bfloat16


def seed_key(seed: int) -> jax.Array:
    s = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(k, s & 0xFFFFFFFF), s >> 32)


def _name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def layer_shapes(dims: Dims, kind: str) -> Dict[str, Tuple[Tuple[int, ...],
                                                           float]]:
    """{name: (shape, std)} of one layer's drawn matrices."""
    d, hd = dims.hidden, dims.head_dim
    out = {"q": ((d, dims.heads * hd), d ** -0.5),
           "k": ((d, dims.kv_heads * hd), d ** -0.5),
           "v": ((d, dims.kv_heads * hd), d ** -0.5),
           "o": ((dims.heads * hd, d), (dims.heads * hd) ** -0.5)}
    if kind == "moe":
        E, f = dims.experts, dims.expert_ff
        out.update(router=((d, E), d ** -0.5),
                   gate=((E, d, f), d ** -0.5), up=((E, d, f), d ** -0.5),
                   down=((E, f, d), f ** -0.5))
        if dims.shared_ff:
            fs = dims.shared_ff
            out.update(s_gate=((d, fs), d ** -0.5), s_up=((d, fs), d ** -0.5),
                       s_down=((fs, d), fs ** -0.5))
    else:
        F = dims.dense_ff
        out.update(d_gate=((d, F), d ** -0.5), d_up=((d, F), d ** -0.5),
                   d_down=((F, d), F ** -0.5))
    return out


def _draw(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(BF16)


def draw_layers(key, dims: Dims, kind: str, layers) -> Dict[str, jax.Array]:
    """The matrices of ``layers`` (all of one kind), stacked on a leading
    layer axis; row i equals ``draw_layer(key, dims, kind, layers[i])``.
    ``layers`` may be traced."""
    ids = jnp.asarray(layers, jnp.uint32)
    out = {}
    for name, (shape, std) in layer_shapes(dims, kind).items():
        nk = _name_key(key, name)
        keys = jax.vmap(lambda i: jax.random.fold_in(nk, i))(ids)
        out[name] = jax.vmap(lambda k: _draw(k, shape, std))(keys)
    return out


def draw_layer(key, dims: Dims, kind: str, layer) -> Dict[str, jax.Array]:
    """One layer's matrices, as bf16; ``layer`` may be traced."""
    return {n: w[0] for n, w in
            draw_layers(key, dims, kind, jnp.reshape(layer, (1,))).items()}


def draw_globals(key, dims: Dims) -> Dict[str, jax.Array]:
    return {"embed": _draw(_name_key(key, "embed"),
                           (dims.vocab, dims.hidden), 1.0),
            "lm_head": _draw(_name_key(key, "lm_head"),
                             (dims.vocab, dims.hidden), dims.hidden ** -0.5)}


def segments(dims: Dims) -> List[Tuple[str, List[int]]]:
    """Runs of consecutive layers of one kind: [(kind, layer ids)]."""
    runs: List[Tuple[str, List[int]]] = []
    for i in range(dims.layers):
        k = dims.kind(i)
        if runs and runs[-1][0] == k:
            runs[-1][1].append(i)
        else:
            runs.append((k, [i]))
    return runs


# --------------------------------------------------------------- the program
def to_program(canon_layers, glob, dims: Dims):
    """The program's parameter tree (``repro.models.model.model_specs``):
    ``segments`` holds one stacked block per run of layers of one kind.
    The program normalises q and k per head with a (head_dim,) scale; the
    scales are ones, as the published ones are drawn."""
    ones = lambda *s: jnp.ones(s, BF16)  # noqa: E731
    hd = dims.head_dim
    segs = []
    for (kind, ids), w in zip(segments(dims), canon_layers):
        n = len(ids)
        mixer = {"wq": {"kernel": w["q"]}, "wk": {"kernel": w["k"]},
                 "wv": {"kernel": w["v"]}, "wo": {"kernel": w["o"]}}
        if dims.qk_norm:
            mixer["q_norm"] = {"scale": ones(n, hd)}
            mixer["k_norm"] = {"scale": ones(n, hd)}
        if kind == "moe":
            ffn = {"router": w["router"].astype(jnp.float32),
                   "wi_gate": w["gate"], "wi_up": w["up"], "wo": w["down"]}
            if dims.shared_ff:
                ffn["shared"] = {"wi_gate": w["s_gate"], "wi_up": w["s_up"],
                                 "wo": w["s_down"]}
        else:
            ffn = {"wi_gate": w["d_gate"], "wi_up": w["d_up"],
                   "wo": w["d_down"]}
        block = {"norm1": {"scale": ones(n, dims.hidden)}, "mixer": mixer,
                 "norm2": {"scale": ones(n, dims.hidden)}, "ffn": ffn}
        segs.append({"blocks": (block,)})
    return {"embed": {"table": glob["embed"]},
            "segments": tuple(segs),
            "final_norm": {"scale": jnp.ones((dims.hidden,), BF16)},
            "lm_head": {"table": glob["lm_head"]}}


def program_params(seed: int, dims: Dims):
    """Draw every weight on the device in one jitted call, in the program's
    layout and served dtype."""
    def make(key):
        layers = [draw_layers(key, dims, kind, ids)
                  for kind, ids in segments(dims)]
        return to_program(layers, draw_globals(key, dims), dims)
    return jax.jit(make)(seed_key(seed))
