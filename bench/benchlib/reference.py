"""The plain reference, and the control that has to fail against it.

The reference is the published architecture's forward pass in jax.numpy,
float32 with the highest matmul precision: embedding, then per layer
RMSNorm -> attention with RoPE (OLMoE: RMSNorm over the whole q and k
projections first) -> residual -> RMSNorm -> FFN -> residual, where the FFN
is a softmax top-k router over SwiGLU experts (weights not renormalised
unless the config says so; DeepSeek adds its shared experts, and its first
layer is a dense SwiGLU), then RMSNorm and the LM head. Norm scales are
ones (as drawn for the program), so the reference leaves them out.

It takes nothing from the program: its weights are drawn again from the
seed, layer by layer (``weights.draw_layer``), after the program's state is
freed. Sequences are packed into one row; attention masks by sequence and
position.

A control is the same pass with every matmul's operands rounded to a
precision below the bf16 the configuration serves in: int8 (each
activation row and each weight column scaled to [-127, 127] and rounded)
or fp8 e4m3 (scaled to its largest finite value, 448).

What is compared, for a served greedy token t after prefix p: how far
t's reference logit lies below the reference's best logit at p.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights
from benchlib.model import Dims

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
PACK = 4096         # packed rows are padded to a multiple of this
Q_BLOCK = 256       # query rows per attention block
T_BLOCK = 512       # tokens per FFN block
READ_BLOCK = 256    # positions per LM-head block


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127), s


def _qfp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32), s


QUANT = {"int8": _q8, "fp8": _qfp8}


def mm(a, w, quant):
    """a (..., k) @ w (k, n) in float32, or, for a control, with each
    activation row and weight column scaled and rounded to ``quant``."""
    if not quant:
        return jnp.matmul(a, w, precision=HIGHEST)
    q = QUANT[quant]
    aq, sa = q(a, -1)
    wq, sw = q(w, 0)
    return jnp.matmul(aq, wq, precision=HIGHEST) * sa * sw


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, pos, theta):
    """x (T, n, hd); the published rotate-half form."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(fn, x, block):
    """fn over row blocks of x (rows a multiple of block)."""
    n = x.shape[0] // block
    out = jax.lax.map(fn, x.reshape(n, block, *x.shape[1:]))
    return out.reshape(n * block, *out.shape[2:])


def attention(x, w, seg, pos, dims: Dims, quant):
    T = x.shape[0]
    H, KV, hd = dims.heads, dims.kv_heads, dims.head_dim
    q = mm(x, w["q"], quant)
    k = mm(x, w["k"], quant)
    v = mm(x, w["v"], quant)
    if dims.qk_norm:
        q = rmsnorm(q, dims.eps)
        k = rmsnorm(k, dims.eps)
    q = rope(q.reshape(T, H, hd), pos, dims.rope_theta)
    k = rope(k.reshape(T, KV, hd), pos, dims.rope_theta)
    v = v.reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)

    def block(args):
        qb, sq, pq = args
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / hd ** 0.5
        keep = (sq[:, None] == seg[None]) & (pos[None] <= pq[:, None])
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    nb = T // Q_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, H, hd),
                            seg.reshape(nb, Q_BLOCK), pos.reshape(nb, Q_BLOCK)))
    return mm(o.reshape(T, H * hd), w["o"], quant)


def swiglu(x, g, u, d, quant):
    return mm(jax.nn.silu(mm(x, g, quant)) * mm(x, u, quant), d, quant)


def _experts_in(x, w, quant):
    """x (t, d) through every expert's (E, d, f) matrix: (t, E, f)."""
    if not quant:
        return jnp.einsum("td,edf->tef", x, w, precision=HIGHEST)
    xq, sx = QUANT[quant](x, -1)
    wq, sw = QUANT[quant](w, 1)
    return (jnp.einsum("td,edf->tef", xq, wq, precision=HIGHEST)
            * sx[:, :, None] * jnp.swapaxes(sw, 0, 1))


def _experts_out(h, w, quant):
    """h (t, E, f) through each expert's (E, f, d) matrix: (t, E, d)."""
    if not quant:
        return jnp.einsum("tef,efd->ted", h, w, precision=HIGHEST)
    hq, sh = QUANT[quant](h, -1)
    wq, sw = QUANT[quant](w, 1)
    return (jnp.einsum("tef,efd->ted", hq, wq, precision=HIGHEST)
            * sh * jnp.swapaxes(sw, 0, 1))


def moe(x, w, dims: Dims, quant):
    """Every expert on every token, weighted by the router's top-k gates
    (zero for experts not chosen)."""
    def block(xb):
        p = jax.nn.softmax(mm(xb, w["router"], quant), axis=-1)
        top, idx = jax.lax.top_k(p, dims.top_k)
        if dims.norm_topk:
            top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.zeros_like(p).at[
            jnp.arange(xb.shape[0])[:, None], idx].set(top)
        h = (jax.nn.silu(_experts_in(xb, w["gate"], quant))
             * _experts_in(xb, w["up"], quant))
        y = jnp.einsum("te,ted->td", gates, _experts_out(h, w["down"], quant),
                       precision=HIGHEST)
        if dims.shared_ff:
            y = y + swiglu(xb, w["s_gate"], w["s_up"], w["s_down"], quant)
        return y

    return _blocks(block, x, T_BLOCK)


def dense_ffn(x, w, quant):
    return _blocks(lambda xb: swiglu(xb, w["d_gate"], w["d_up"], w["d_down"],
                                     quant), x, T_BLOCK)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def layer(x, w, seg, pos, dims: Dims, kind: str, quant: Optional[str]):
    with jax.default_matmul_precision("highest"):
        w = {n: a.astype(F32) for n, a in w.items()}
        x = x + attention(rmsnorm(x, dims.eps), w, seg, pos, dims, quant)
        h = rmsnorm(x, dims.eps)
        return x + (moe(h, w, dims, quant) if kind == "moe"
                    else dense_ffn(h, w, quant))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _draw(key, layer_id, dims: Dims, kind: str):
    return weights.draw_layer(key, dims, kind, layer_id)


@functools.partial(jax.jit, static_argnums=(5, 6))
def read(x, xcs, lm_head, rows, targets, dims: Dims, controls: tuple):
    """Per read position: the best reference logit, the reference logit of
    the target and, for each control, the reference logit of the
    control's best."""
    head = lm_head.astype(F32)

    def block(args):
        r, t = args
        lr = mm(rmsnorm(x[r], dims.eps), head.T, None)
        take = lambda idx: jnp.take_along_axis(lr, idx[:, None], 1)[:, 0]  # noqa: E731
        out = (jnp.max(lr, -1), take(t))
        for xc, q in zip(xcs, controls):
            lc = mm(rmsnorm(xc[r], dims.eps), head.T, q)
            out += (take(jnp.argmax(lc, axis=-1)),)
        return out

    nb = rows.shape[0] // READ_BLOCK
    out = jax.lax.map(block, (rows.reshape(nb, READ_BLOCK),
                              targets.reshape(nb, READ_BLOCK)))
    return tuple(o.reshape(-1) for o in out)


def pack(seqs: Sequence[Tuple[Sequence[int], Sequence[int]]]):
    """Pack (prompt, served tokens) pairs: each row holds the prompt and all
    served tokens but the last; a served token is read at the position
    before it. Returns tokens, seg, pos, read rows, targets, n_read."""
    toks, seg, pos, rows, targets = [], [], [], [], []
    base = 0
    for i, (prompt, out) in enumerate(seqs):
        s = list(prompt) + list(out[:-1])
        toks += s
        seg += [i] * len(s)
        pos += range(len(s))
        rows += [base + len(prompt) - 1 + j for j in range(len(out))]
        targets += list(out)
        base += len(s)
    T = -(-base // PACK) * PACK
    pad = T - base
    n_read = len(rows)
    R = -(-n_read // READ_BLOCK) * READ_BLOCK
    return (np.asarray(toks + [0] * pad, np.int32),
            np.asarray(seg + [-1] * pad, np.int32),
            np.asarray(pos + [0] * pad, np.int32),
            np.asarray(rows + [0] * (R - n_read), np.int32),
            np.asarray(targets + [0] * (R - n_read), np.int32), n_read)


def compare(seed: int, dims: Dims, seqs,
            controls: Sequence[str] = ()) -> dict:
    """The served tokens' gaps below the reference's best logit and, for
    each control ("int8", "fp8"), the gaps of its first choices at the
    same positions."""
    toks, seg, pos, rows, targets, n = pack(seqs)
    controls = tuple(controls)
    key = weights.seed_key(seed)
    glob = jax.jit(weights.draw_globals, static_argnums=1)(key, dims)
    x = glob["embed"][jnp.asarray(toks)].astype(F32)
    xcs = [x] * len(controls)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    for i in range(dims.layers):
        kind = dims.kind(i)
        w = _draw(key, jnp.uint32(i), dims, kind)
        x = layer(x, w, seg, pos, dims, kind, None)
        xcs = [layer(xc, w, seg, pos, dims, kind, q)
               for xc, q in zip(xcs, controls)]
        del w
    got = [np.asarray(a)[:n] for a in read(
        x, tuple(xcs), glob["lm_head"], jnp.asarray(rows),
        jnp.asarray(targets), dims, controls)]
    out = {"tokens": int(n), "gaps": got[0] - got[1]}
    for q, served in zip(controls, got[2:]):
        out[q] = got[0] - served
    return out


def widest(gaps: np.ndarray) -> float:
    return float(np.max(gaps)) if len(gaps) else float("nan")
