"""A configuration's sizes, read from its file alone (the published
config.json keys), for the reference and the arithmetic."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    family: str          # "olmoe" | "deepseek"
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    eps: float
    rope_theta: float
    experts: int
    top_k: int
    expert_ff: int
    shared_ff: int       # all shared experts together, 0 if none
    dense_ff: int        # width of the leading dense layers' FFN
    first_dense: int     # leading dense layers
    norm_topk: bool
    qk_norm: bool        # OLMoE: RMSNorm over the whole q and k projections

    def kind(self, layer: int) -> str:
        return "dense" if layer < self.first_dense else "moe"

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense


def dims_of(cfg: dict) -> Dims:
    """Sizes from a configuration file's published keys."""
    family = cfg["model_type"]
    common = dict(hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                  kv_heads=cfg["num_key_value_heads"],
                  head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                  layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                  eps=float(cfg["rms_norm_eps"]),
                  rope_theta=float(cfg["rope_theta"]),
                  top_k=cfg["num_experts_per_tok"],
                  norm_topk=bool(cfg["norm_topk_prob"]))
    if family == "olmoe":
        return Dims(family=family, experts=cfg["num_experts"],
                    expert_ff=cfg["intermediate_size"], shared_ff=0,
                    dense_ff=0, first_dense=0, qk_norm=True, **common)
    if family == "deepseek":
        return Dims(family=family, experts=cfg["n_routed_experts"],
                    expert_ff=cfg["moe_intermediate_size"],
                    shared_ff=(cfg["n_shared_experts"]
                               * cfg["moe_intermediate_size"]),
                    dense_ff=cfg["intermediate_size"],
                    first_dense=cfg["first_k_dense_replace"], qk_norm=False,
                    **common)
    raise ValueError(f"no reference for model_type {family!r}")
