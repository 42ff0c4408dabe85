"""Operations and bytes of a stage's work, from its shapes alone.

A stage is a list of rows, as the harness reads them off the requests:
``("decode", ctx)`` — one new token attending ``ctx`` positions (itself
included) — and ``("chunk", start, end)`` — prompt positions
[start, end) attending everything before them. Counts are of the work the
algorithm needs (live tokens, no padding), over all layers; bytes assume
the served bf16 weights and KV.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from benchlib.model import Dims

BYTES = 2   # bf16


def attn_work(dims: Dims, rows: Iterable[tuple]) -> Tuple[float, float]:
    """(FLOPs, bytes) of the attention kernel's calls: QK^T and PV over the
    live context; K and V of the context read once, q read and the output
    written once, per layer."""
    H, KV, hd, L = dims.heads, dims.kv_heads, dims.head_dim, dims.layers
    flops = nbytes = 0.0
    for r in rows:
        if r[0] == "decode":
            ctx, pairs, q = r[1], r[1], 1
        else:
            s, e = r[1], r[2]
            q = e - s
            ctx = e
            pairs = q * s + q * (q + 1) / 2      # causal (query, key) pairs
        flops += 4.0 * H * hd * pairs
        nbytes += BYTES * (2 * ctx * KV * hd + 2 * q * H * hd)
    return flops * L, nbytes * L


def live_tokens(rows: Iterable[tuple]) -> int:
    return sum(1 if r[0] == "decode" else r[2] - r[1] for r in rows)


def experts_touched(dims: Dims, tokens: int) -> float:
    """Expected experts with at least one of ``tokens`` tokens in a layer,
    each token taking top_k distinct experts uniformly: the routing of
    seeded random weights."""
    E, k = dims.experts, dims.top_k
    return E * (1.0 - (1.0 - k / E) ** tokens)


def moe_work(dims: Dims, tokens: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed-expert kernels over all MoE layers:
    each token's top_k SwiGLU experts (3 matmuls); each touched expert's
    weights read once, each routed token read and its output written."""
    d, f, k = dims.hidden, dims.expert_ff, dims.top_k
    flops = 2.0 * 3 * d * f * k * tokens
    nbytes = BYTES * (3 * d * f * experts_touched(dims, tokens)
                      + 2 * k * tokens * d)
    return flops * dims.moe_layers, nbytes * dims.moe_layers


def active_params(dims: Dims) -> Tuple[float, float]:
    """(params every token runs through in the layers, LM-head params)."""
    d, hd = dims.hidden, dims.head_dim
    attn = d * hd * (2 * dims.heads + 2 * dims.kv_heads)
    moe = (3 * d * dims.expert_ff * dims.top_k + d * dims.experts
           + 3 * d * dims.shared_ff)
    dense = 3 * d * dims.dense_ff
    layers = (dims.layers * attn + dims.moe_layers * moe
              + dims.first_dense * dense)
    return float(layers), float(dims.vocab * d)


def model_flops(dims: Dims, rows: Iterable[tuple]) -> float:
    """FLOPs the model needs for a stage: 2 per active parameter per live
    token, the LM head for each token that yields a logit (a decode row or
    a chunk that ends its prompt: ``("chunk", s, e, True)``), and attention
    over the context."""
    rows = list(rows)
    body, head = active_params(dims)
    logits = sum(1 for r in rows if r[0] == "decode" or
                 (len(r) > 3 and r[3]))
    attn, _ = attn_work(dims, rows)
    return 2.0 * body * live_tokens(rows) + 2.0 * head * logits + attn
