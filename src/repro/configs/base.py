"""Model/config schema shared by every assigned architecture.

A single dataclass covers all five families (dense / moe / hybrid / ssm /
vlm / audio); family-specific fields default to "off".  Every arch file in
this package exports ``CONFIG`` (the exact published shape) and ``TINY``
(a reduced same-family config for CPU smoke tests).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class ModelConfig:
    # --- identity -----------------------------------------------------
    name: str
    family: str                      # dense | moe | hybrid | ssm | vlm | audio
    # --- backbone -----------------------------------------------------
    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    # --- mixture of experts --------------------------------------------
    num_experts: int = 0             # 0 = dense MLP
    experts_per_token: int = 0       # top-k
    moe_interleave: int = 1          # MoE every k-th layer (llama4: 2)
    capacity_factor: float = 1.25    # training's grouped routing only
    moe_group: int = 512             # routing group size (dispatch cost ∝ group)
    moe_renorm: bool = True          # top-k gates renormalized to sum 1
                                     # (step3: norm_expert_weight false)
    shared_expert_ff: int = 0        # width of an always-on shared expert
    # expert parallelism: this chip holds routed experts
    # [experts_held_start, experts_held_start + experts_held) of the
    # router's num_experts (0 = all of them) and computes their part
    experts_held: int = 0
    experts_held_start: int = 0
    # --- attention ------------------------------------------------------
    sliding_window: int = 0          # 0 = full attention
    rope_theta: float = 1_000_000.0
    attn_chunk: int = 1024           # query-chunked attention block size
    q_lora_rank: int = 0             # low-rank query: D -> rank, RMSNorm,
                                     # -> heads (step3's share_q_dim); 0 = one wq
    norm_eps: float = 1e-6           # every RMSNorm of the decoder
    # --- mlp ------------------------------------------------------------
    mlp_activation: str = "silu"     # silu | gelu | relu2
    gated_mlp: bool = True
    parallel_block: bool = False     # stablelm-2 style parallel attn+mlp
    # --- hybrid (jamba) --------------------------------------------------
    attn_period: int = 0             # one attention layer per `attn_period` layers
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0           # 0 -> d_model // 16
    # --- rwkv -------------------------------------------------------------
    rwkv_head_dim: int = 64
    rwkv_decay_lora: int = 64
    rwkv_chunk: int = 0              # 0 = step-scan; >0 = chunked matmul wkv
    # --- encoder/decoder (whisper) -----------------------------------------
    encoder_layers: int = 0
    encoder_seq: int = 1500          # precomputed frame embeddings (stub frontend)
    cross_attention: bool = False
    # --- io ------------------------------------------------------------------
    input_mode: str = "tokens"       # tokens | embeddings (vlm/audio stub frontend)
    tie_embeddings: bool = False
    # --- numerics / memory ------------------------------------------------
    dtype: str = "bfloat16"
    remat: str = "full"              # none | full
    # nested (sqrt-L) remat: checkpoint groups of `remat_group` period
    # blocks — layer-boundary activations drop G×, backward recomputes a
    # G-block span once.  §Perf hillclimb lever; 1 = plain per-layer remat.
    remat_group: int = 1
    # attention scores dtype for the SP (unchunked) path; bf16 halves the
    # (B, K, G, S/16, S) transient at 32k prefill
    sp_scores_bf16: bool = False
    # --- parallel layout -----------------------------------------------------
    # "tp": shard heads/mlp over 'model'.  "sp": shard the sequence over
    # 'model' (Ulysses-style) — used when num_heads doesn't divide the
    # model axis (llama3.2: 24H, llama4: 40H on a 16-way axis), where TP
    # would silently replicate all attention compute.  "auto" resolves
    # per mesh.
    layout: str = "auto"
    # --- serving ------------------------------------------------------------
    max_decode_window: int = 0       # SWA archs: rolling cache size (0 = seq_len)
    # --- provenance ----------------------------------------------------------
    source: str = ""

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.mamba_dt_rank == 0 and self.family == "hybrid":
            object.__setattr__(self, "mamba_dt_rank", max(1, self.d_model // 16))

    # Convenience ------------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def n_held_experts(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.num_experts

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def rwkv_heads(self) -> int:
        return self.d_model // self.rwkv_head_dim

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def digest(self) -> str:
        """Stable hash of the config — keys the dry-run cache."""
        blob = json.dumps(dataclasses.asdict(self), sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]


@dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell: what gets lowered and at what size."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: dict[str, ShapeConfig] = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",   524_288, 1,   "decode"),
}


def resolve_layout(cfg: ModelConfig, model_axis: int = 16) -> str:
    """tp: heads/mlp over 'model'.  sp: sequence over 'model' (heads
    don't divide).  sp2: sp + 2D expert sharding (EP over 'data', expert
    FFN over 'model') — no FSDP gather of expert weights."""
    if cfg.layout != "auto":
        return cfg.layout
    if cfg.family == "ssm" or cfg.num_heads == 0:
        return "tp"
    return "tp" if cfg.num_heads % model_axis == 0 else "sp"


def sub_quadratic(cfg: ModelConfig) -> bool:
    """Archs that may run the long_500k decode cell (see DESIGN.md §4)."""
    return (
        cfg.family in ("ssm", "hybrid")
        or cfg.sliding_window > 0
    )


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    if shape.name == "long_500k":
        return sub_quadratic(cfg)
    return True
