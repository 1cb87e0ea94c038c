"""Unified architecture configuration covering all assigned families."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    arch_type: str = "dense"     # dense | moe | ssm | hybrid | encoder | vlm | audio
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv: int = 4
    head_dim: int = 0            # 0 -> d_model // n_heads
    d_ff: int = 1024
    vocab: int = 1024
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    causal: bool = True          # False for encoder-only (hubert)
    # local/global attention pattern: `window > 0` enables sliding-window layers;
    # every `global_every`-th layer (1-based) is full/global attention.
    window: int = 0
    global_every: int = 0        # 0 -> all layers share `window` (or all full)
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    d_expert: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_dispatch: str = "dense"  # dense | sharded (shard_map local dispatch)
    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 64
    conv_width: int = 4
    # hybrid (RecurrentGemma): repeating block pattern, e.g. ("rec","rec","attn")
    block_pattern: Tuple[str, ...] = ()
    lru_width: int = 0           # 0 -> d_model
    # modality frontend stub
    frontend: str = "none"       # none | audio | vision
    n_patches: int = 256         # vision: patches prepended to the text sequence
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    # distribution policy
    fsdp: bool = False           # shard large-matrix non-model dims over pod×data
    moe_shard: str = "ep"        # ep: experts over model axis | tp: expert-hidden over model
    dtype: str = "float32"       # parameter / activation dtype
    scan_layers: bool = True     # stack+scan homogeneous layer groups
    remat: bool = False          # activation checkpointing on each layer group
    # Pallas kernels: None lets the backend decide (kernels/backend.py) —
    # Mosaic kernels on TPU, the jnp paths and the interpreter elsewhere
    use_pallas_decode: Optional[bool] = None  # attention kernels (kernels/swa.py)
    # SSD intra-chunk kernel (kernels/ssd.py): off unless asked for — Mosaic
    # does not lower it yet (kernels/README.md)
    use_pallas_ssm: bool = False
    pallas_interpret: Optional[bool] = None

    # ------------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.ssm_heads or (self.d_inner // self.ssm_head_dim)

    @property
    def pattern(self) -> Tuple[str, ...]:
        """Per-layer kind pattern of length == one repeating group."""
        if self.arch_type == "hybrid" and self.block_pattern:
            return self.block_pattern
        if self.arch_type == "ssm":
            return ("ssm",)
        if self.global_every and self.window:
            # gemma3-style: (global_every - 1) local layers then 1 global
            return tuple(["local"] * (self.global_every - 1) + ["global"])
        if self.window:
            return ("local",)
        return ("attn",)

    def layer_kinds(self) -> Tuple[str, ...]:
        p = self.pattern
        return tuple(p[i % len(p)] for i in range(self.n_layers))

    def is_subquadratic(self) -> bool:
        """True if a 500k-token decode is feasible (no full-attention KV growth),
        i.e. every layer is local/recurrent/ssm OR global layers are O(S)-decode
        with a sliding-window majority (gemma3's 5:1)."""
        kinds = set(self.layer_kinds())
        return kinds.issubset({"ssm", "rec", "local"}) or (
            "local" in kinds and self.window > 0
        )

    def supports_decode(self) -> bool:
        return self.causal and self.arch_type not in ("encoder", "audio")

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)
