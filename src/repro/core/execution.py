"""Execution-plan context: selects the execution path per layer family.

The serving engine (and the dry-run/benchmarks) trace step functions under an
``execution_plan(...)`` context; model code consults the active plan to pick:

  * MoE implementation: ``grouped`` (paper-baseline xPU path) or ``duplex``
    (expert co-processing, C2) with its static planner outputs (k_cold,
    capacities);
  * whether attention/MoE lower through the Pallas kernels (TPU) or the XLA
    reference paths (CPU container, dry-run).

This is the C1 dispatch decision made concrete: `core/dispatch.py` picks the
paths from Op/B; the chosen StagePlan is rendered into an ExecutionPlan that
the jitted stage function is traced under.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
from dataclasses import dataclass, replace
from typing import Optional

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class ExecutionPlan:
    moe_impl: str = "grouped"        # grouped | duplex
    k_cold: int = 0                  # duplex: # cold (bandwidth-path) experts
    c_hot: Optional[int] = None      # duplex: hot capacity (None = auto)
    c_cold: Optional[int] = None     # duplex: cold capacity (None = auto)
    moe_capacity: Optional[int] = None   # grouped: capacity override
    # duplex + kernels: thread per-expert live counts into the ragged
    # scalar-prefetch MoE kernels (dead token-block DMAs elided, compute
    # skipped) instead of the capacity-padded grouped GEMM.
    moe_ragged: bool = False
    moe_c_block: int = 256           # hot grouped-GEMM token-block size
    use_kernels: bool = False        # Pallas kernels (TPU) vs XLA paths
    decode_kv_block: int = 512
    # hierarchical MoE dispatch: tokens dispatch into per-shard slot blocks so
    # the token->slot gather/scatter stays shard-local (no global gather,
    # which GSPMD lowers to full-buffer all-reduces). (batch-shard count,
    # seq-shard count) of the activation layout; (1, 1) = single-device.
    dispatch_grid: tuple = (1, 1)
    # blockwise-attention tile shapes + score-chain precision (SPerf knobs)
    attn_q_block: int = 512
    attn_kv_block: int = 512
    attn_score_bf16: bool = False


DEFAULT_PLAN = ExecutionPlan()

_PLAN: contextvars.ContextVar = contextvars.ContextVar("execution_plan",
                                                       default=DEFAULT_PLAN)


@contextlib.contextmanager
def execution_plan(plan: ExecutionPlan):
    token = _PLAN.set(plan)
    try:
        yield plan
    finally:
        _PLAN.reset(token)


def current_plan() -> ExecutionPlan:
    return _PLAN.get()


# the expert weight tables of an MoE block's params
EXPERT_TABLES = ("wi_gate", "wi_up", "wi", "wo")

_MOE_TALLY: contextvars.ContextVar = contextvars.ContextVar("moe_lowering",
                                                            default=None)


@contextlib.contextmanager
def moe_lowering_tally():
    """Count, while a step function is traced, how its MoE layers lower:
    ``inplace`` (the expert kernels read the parameter tables where they
    lie) or ``gathered`` (a copy of the layer's tables first: the XLA and
    grouped paths, or a d_ff no kernel tile divides). Trace-time Python
    only; the traced program carries nothing of it."""
    tally = collections.Counter()
    token = _MOE_TALLY.set(tally)
    try:
        yield tally
    finally:
        _MOE_TALLY.reset(token)


def note_moe_lowering(kind: str, layers: int) -> None:
    tally = _MOE_TALLY.get()
    if tally is not None:
        tally[kind] += layers


def shard_blocks(x):
    """(B, S, d) -> (n, Tl, d) where each row is one (batch-block, seq-block)
    tile of the active plan's dispatch grid — aligned with the activation
    sharding so downstream token gathers stay shard-local. Returns
    (xb, restore) with ``restore`` undoing the blocking on a (T, d) array."""
    import jax.numpy as jnp

    grid = current_plan().dispatch_grid
    B, S, d = x.shape

    def divisor(dim, limit):
        n = max(1, min(limit, dim))
        while dim % n:
            n -= 1
        return n

    nb, ns = divisor(B, grid[0]), divisor(S, grid[1])
    if nb * ns == 1:
        return x.reshape(1, B * S, d), lambda y: y.reshape(B, S, d)
    xb = x.reshape(nb, B // nb, ns, S // ns, d)
    xb = xb.transpose(0, 2, 1, 3, 4).reshape(nb * ns, -1, d)

    def restore(y_flat):
        y = y_flat.reshape(nb, ns, B // nb, S // ns, d)
        return y.transpose(0, 2, 1, 3, 4).reshape(B, S, d)

    return xb, restore


def moe_execute(params, cfg: ModelConfig, x, *, return_stats: bool = False,
                token_valid=None, layer=None):
    """Route the MoE layer through the path the active plan selects.
    ``token_valid`` (flat-token bool mask) excludes padded serving rows from
    routing counts and expert capacity on either path. With ``layer`` (a
    traced int32 scalar) the expert tables in ``params`` are the segment's
    whole (L, E, ., .) stacks and ``layer`` picks the row of this layer."""
    plan = current_plan()
    # the ragged kernels live on the count-threaded duplex path, so a
    # duplex plan with k_cold == 0 still routes there when ragged is on
    # (all experts hot, all token blocks count-gated).
    if plan.moe_impl == "duplex" and (plan.k_cold > 0 or plan.moe_ragged):
        from repro.core.duplex_moe import duplex_moe_apply
        return duplex_moe_apply(params, cfg, x, k_cold=plan.k_cold,
                                c_hot=plan.c_hot, c_cold=plan.c_cold,
                                use_kernels=plan.use_kernels,
                                ragged=plan.moe_ragged,
                                c_block=plan.moe_c_block,
                                return_stats=return_stats,
                                token_valid=token_valid, layer=layer)
    from repro.models.moe import moe_apply
    if layer is not None:
        keys = [k for k in EXPERT_TABLES if k in params]
        note_moe_lowering("gathered", params[keys[0]].shape[0])
        params = {**params, **{k: params[k][layer] for k in keys}}
    return moe_apply(params, cfg, x, capacity=plan.moe_capacity,
                     return_stats=return_stats, token_valid=token_valid)
