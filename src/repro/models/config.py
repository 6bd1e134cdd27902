"""Unified model configuration covering all assigned architecture families.

One dataclass; family-specific fields are ignored by families that don't use
them.  Every assigned architecture instantiates this in
``repro/configs/<id>.py``; ``reduced()`` derives the CPU smoke-test config.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass
class MoEConfig:
    """Routed experts beside optional shared ones.  The router scores all
    ``num_experts``; this device holds ``held_experts`` of them (None: all),
    from index ``first_held_expert`` on, and computes their part of each
    layer's output (expert parallelism, one device's share).  Leading
    ``first_dense_layers`` use a dense FFN of ``ModelConfig.d_ff``."""

    num_experts: int = 0
    experts_per_token: int = 0
    d_ff_expert: int = 0
    num_shared_experts: int = 0
    first_dense_layers: int = 0
    held_experts: Optional[int] = None
    first_held_expert: int = 0
    norm_topk_prob: bool = True  # renormalize the top-k gates to sum to 1
    router_aux_weight: float = 0.01

    @property
    def held(self) -> int:
        return self.num_experts if self.held_experts is None else self.held_experts


@dataclasses.dataclass
class SSMConfig:
    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 128

    def num_heads(self, d_model: int) -> int:
        return self.expand * d_model // self.head_dim

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model


@dataclasses.dataclass
class YarnScaling:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it
    (``rope_scaling`` with ``type: yarn``)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: int = 0  # 0 = full-rank q projection
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_scaling: Optional[YarnScaling] = None  # on the rope dims


@dataclasses.dataclass
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // num_heads
    attention: str = "gqa"  # gqa | mla | none
    act: str = "silu"  # silu | gelu | geglu(=gelu-gated)
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    logit_soft_cap: Optional[float] = None
    sliding_window: Optional[int] = None  # applied to non-global attn layers
    global_attn_every: int = 0  # 0 = all layers global (no windowing)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    # encoder-decoder (audio family)
    encoder_layers: int = 0
    is_encoder_decoder: bool = False
    # modality frontend stub: inputs arrive as precomputed embeddings
    frontend: str = "none"  # none | patch(vlm) | audio(frames)
    frontend_seq: int = 0  # prefix length delivered by the stub frontend
    dtype: str = "bfloat16"
    kernel_backend: str = "auto"  # pallas | xla | auto (see kernels.ops)
    # Paged-KV storage format: None = store cfg.dtype; "int8"/"int4" = packed
    # symmetric per-token quantization with per-row scales kept in the page
    # pools (see kernels.ref.quantize_rows / DESIGN.md §5.6).  Only the paged
    # layouts support this; contiguous caches reject it loudly.
    kv_dtype: Optional[str] = None

    # -- derived -----------------------------------------------------------
    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.d_model // max(self.num_heads, 1)

    @property
    def attends(self) -> bool:
        return self.attention != "none"

    @property
    def kv_head_dim(self) -> int:
        """Width of one cached KV head's row: ``head_dim``, or MLA's latent
        rank plus its rope dims (absorbed MLA attends one such head)."""
        if self.attention == "mla":
            return self.mla.kv_lora_rank + self.mla.qk_rope_head_dim
        return self.head_dim

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run 500k-token decode? (SSM / hybrid / windowed.)"""
        if self.family in ("ssm",):
            return True
        if self.family == "hybrid":
            return True
        return False

    def window_for_layer(self, layer: int) -> Optional[int]:
        if self.sliding_window is None:
            return None
        if self.global_attn_every and (layer + 1) % self.global_attn_every == 0:
            return None  # periodic global layer
        return self.sliding_window

    # -- parameter counting (roofline MODEL_FLOPS = 6*N*D uses these) --------
    def param_count(self, active_only: bool = False) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.head_dim
        n = 0
        n += v * d  # embedding
        if not self.tie_embeddings:
            n += v * d
        per_layer = 0
        if self.attends and self.attention == "gqa":
            per_layer += d * self.num_heads * hd  # q
            per_layer += 2 * d * self.num_kv_heads * hd  # kv
            per_layer += self.num_heads * hd * d  # o
        elif self.attention == "mla":
            m = self.mla
            qd = self.num_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
            per_layer += d * qd if not m.q_lora_rank else d * m.q_lora_rank + m.q_lora_rank * qd
            per_layer += d * (m.kv_lora_rank + m.qk_rope_head_dim)
            per_layer += m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
            per_layer += self.num_heads * m.v_head_dim * d
        if self.ssm is not None:
            di = self.ssm.d_inner(d)
            nh = self.ssm.num_heads(d)
            per_layer += d * (2 * di + 2 * self.ssm.state_dim * (1 if self.family == "ssm" else 1) + nh)
            per_layer += di * d
        per_layer += 2 * d  # norms
        dense_ffn = (3 if self.act in ("silu", "geglu") else 2) * d * f
        n_moe = 0
        if self.moe is not None and self.moe.num_experts:
            mo = self.moe
            n_moe = self.num_layers - mo.first_dense_layers
            experts = min(mo.experts_per_token, mo.held) if active_only else mo.held
            n += n_moe * (
                (experts + mo.num_shared_experts) * 3 * d * mo.d_ff_expert
                + d * mo.num_experts  # router
            )
        n += self.num_layers * per_layer + (self.num_layers - n_moe) * dense_ffn
        if self.is_encoder_decoder:
            # encoder self-attn + ffn, and decoder cross-attention extras
            n += self.encoder_layers * (
                2 * d * self.num_heads * hd
                + 2 * d * self.num_kv_heads * hd
                + 2 * d * f
                + 2 * d
            )
            n += self.num_layers * (
                2 * d * self.num_heads * hd  # cross-attn q & o
                + 2 * d * self.num_kv_heads * hd  # cross-attn k & v
                + 2 * d
            )
        return n

    # -- smoke-test reduction -------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        cfg = dataclasses.replace(
            self,
            num_layers=2,
            d_model=64,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2) or 1,
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab_size=256,
            encoder_layers=2 if self.is_encoder_decoder else 0,
            sliding_window=32 if self.sliding_window else None,
            frontend_seq=8 if self.frontend != "none" else 0,
            dtype="float32",
            kernel_backend="xla",
        )
        if cfg.moe is not None:
            cfg.moe = dataclasses.replace(
                cfg.moe, num_experts=4, experts_per_token=2, d_ff_expert=32,
                num_shared_experts=min(cfg.moe.num_shared_experts, 1),
                first_dense_layers=min(cfg.moe.first_dense_layers, 1),
                held_experts=None, first_held_expert=0,
            )
        if cfg.ssm is not None:
            cfg.ssm = dataclasses.replace(
                cfg.ssm, state_dim=16, head_dim=16, conv_width=4, chunk=16
            )
        if cfg.mla is not None:
            cfg.mla = dataclasses.replace(
                cfg.mla, kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16,
            )
        return cfg
