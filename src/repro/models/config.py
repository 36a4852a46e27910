"""Model configuration dataclasses for every supported family."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN hidden
    num_shared: int = 0           # always-on shared experts (deepseek)
    d_shared: int = 0             # shared-expert FFN hidden (total)
    router_dtype: str = "float32"
    expert_mode: str = "tp"       # 'tp' (shard d_expert) | 'ep' (shard experts)
    capacity_factor: float = 1.25  # 0 => dropless (sort + ragged_dot)
    # 'softmax' | 'sigmoid' (DeepSeek-V3 / GLM-4.5 ``noaux_tc``: sigmoid
    # scores, a per-expert bias that only chooses the top k, the chosen
    # raw scores normalised)
    scoring: str = "softmax"
    routed_scale: float = 1.0     # routed_scaling_factor on the routed sum
    # (first, count) of the routed experts this device holds; None => all.
    # Routing still spans num_experts; absent experts contribute nothing.
    held: Optional[Tuple[int, int]] = None

    SCORINGS = ("softmax", "sigmoid")

    def __post_init__(self):
        if isinstance(self.held, list):           # from a JSON file
            object.__setattr__(self, "held", tuple(self.held))
        if self.scoring not in self.SCORINGS:
            raise ValueError(f"MoEConfig.scoring={self.scoring!r} not in "
                             f"{self.SCORINGS}")
        if self.held is not None:
            first, count = self.held
            if not (0 <= first and count >= 1
                    and first + count <= self.num_experts):
                raise ValueError(f"MoEConfig.held={self.held} is not a range "
                                 f"of the {self.num_experts} experts")
            if self.capacity_factor > 0:
                raise ValueError("MoEConfig.held needs the dropless "
                                 "dispatch (capacity_factor=0)")

    @property
    def n_held(self) -> int:
        return self.num_experts if self.held is None else self.held[1]


@dataclasses.dataclass(frozen=True)
class MLAConfig:                  # Multi-head Latent Attention (MiniCPM3/DeepSeek)
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int


@dataclasses.dataclass(frozen=True)
class SSMConfig:                  # mamba2 / SSD
    d_state: int
    d_inner: int                  # = heads * head_p
    head_p: int = 64              # P, per-head channels
    conv_kernel: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1

    @property
    def heads(self) -> int:
        return self.d_inner // self.head_p


@dataclasses.dataclass(frozen=True)
class EncoderConfig:              # whisper-style frame encoder (frontend = stub)
    n_layers: int
    n_frames: int = 1500          # post-conv frame count the stub emits


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int                     # MLP width (MoE archs: of the dense layers)
    vocab: int
    head_dim: int = 0             # 0 => d_model // n_heads
    mixer: str = "attn"           # attn | ssm | hybrid
    mlp_kind: str = "swiglu"      # swiglu | gelu
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0    # glm4 rotates half the head dim
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl
    norm_eps: float = 1e-5
    window: int = 0               # 0 => full causal; else sliding window
    global_layers: Tuple[int, ...] = ()   # layers that override window -> full
    moe: Optional[MoEConfig] = None
    # leading layers with a dense d_ff MLP before the MoE stack
    # (``first_k_dense_replace``); the first ``dense_layers`` of n_layers
    dense_layers: int = 0
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    tie_embeddings: bool = False
    subquadratic: bool = False    # eligible for long_500k shapes
    norm_bf16_grad: bool = False  # perf: bf16 cotangent out of RMSNorm
    # auto | jnp | interpret | pallas — kernels/flash is fwd+bwd
    # differentiable (custom_vjp with O(S*D) residuals), so "pallas" is
    # legal for training; "auto" picks by platform (see attn_impl)
    attn_backend: str = "auto"

    ATTN_BACKENDS = ("auto", "jnp", "interpret", "pallas")

    def __post_init__(self):
        # nested configs may be given as dicts (a JSON file's overrides)
        for name, kind in (("moe", MoEConfig), ("mla", MLAConfig)):
            if isinstance(getattr(self, name), dict):
                object.__setattr__(self, name, kind(**getattr(self, name)))
        if self.head_dim == 0 and self.n_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.dense_layers and (self.moe is None or not self.d_ff
                                  or self.dense_layers >= self.n_layers
                                  or self.global_layers):
            raise ValueError("dense_layers needs a MoE arch with a dense "
                             "d_ff, a uniform window and at least one MoE "
                             "layer after them")
        if self.attn_backend not in self.ATTN_BACKENDS:
            raise ValueError(
                f"attn_backend={self.attn_backend!r} not in "
                f"{self.ATTN_BACKENDS}")

    def attn_impl(self) -> str:
        """The attention path that runs: ``attn_backend`` with "auto"
        resolved by platform — the compiled flash kernel on a TPU, jnp
        elsewhere (``interpret`` is only ever chosen explicitly)."""
        if self.attn_backend != "auto":
            return self.attn_backend
        from repro.kernels import platform_backend
        return "pallas" if platform_backend() == "pallas" else "jnp"

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 256 so the embedding/LM-head
        can always shard over a <=256-way model axis (standard TP padding;
        rows beyond ``vocab`` are dead weight, logits there are masked)."""
        return -(-self.vocab // 256) * 256

    # ------------------------------------------------------------- sizing --
    def param_count(self) -> int:
        """Analytic parameter count (embeddings included once if tied)."""
        d, l = self.d_model, self.n_layers
        total = self.vocab * d                     # embed
        if not self.tie_embeddings:
            total += self.vocab * d                # lm head
        total += d                                 # final norm
        per_layer = 0
        if self.mixer in ("attn", "hybrid"):
            per_layer += d                         # ln1
            if self.mla is not None:
                m = self.mla
                per_layer += d * m.q_lora_rank + m.q_lora_rank
                per_layer += m.q_lora_rank * self.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
                per_layer += d * (m.kv_lora_rank + m.qk_rope_dim) + m.kv_lora_rank
                per_layer += m.kv_lora_rank * self.n_heads * (m.qk_nope_dim + m.v_head_dim)
                per_layer += self.n_heads * m.v_head_dim * d
            else:
                hd = self.head_dim
                per_layer += d * self.n_heads * hd          # wq
                per_layer += 2 * d * self.n_kv * hd         # wk, wv
                per_layer += self.n_heads * hd * d          # wo
        if self.mixer in ("ssm", "hybrid"):
            s = self.ssm
            per_layer += d  # ln (shared with ln1 in hybrid; close enough)
            conv_dim = s.d_inner + 2 * s.d_state
            per_layer += d * (2 * s.d_inner + 2 * s.d_state + s.heads)  # in_proj
            per_layer += conv_dim * s.conv_kernel                        # conv
            per_layer += 3 * s.heads                                     # A, D, dt_bias
            per_layer += s.d_inner                                       # gated norm
            per_layer += s.d_inner * d                                   # out_proj
        # FFN
        per_layer += d                             # ln2
        mult = 3 if self.mlp_kind == "swiglu" else 2
        if self.moe is not None:
            m = self.moe
            total += self.dense_layers * (per_layer + mult * d * self.d_ff)
            l -= self.dense_layers
            per_layer += d * m.num_experts                               # router
            if m.scoring == "sigmoid":
                per_layer += m.num_experts                               # bias
            per_layer += m.n_held * 3 * d * m.d_expert                   # experts
            if m.num_shared:
                per_layer += 3 * d * m.d_shared                          # shared
        elif self.d_ff:
            per_layer += mult * d * self.d_ff
        total += l * per_layer
        if self.encoder is not None:
            hd = self.head_dim
            enc_layer = 2 * d + d * self.n_heads * hd + 2 * d * self.n_kv * hd \
                + self.n_heads * hd * d + 2 * d * self.d_ff
            # decoder cross-attention adds another attn block per layer
            total += self.encoder.n_layers * enc_layer + d
            total += l * (d + d * self.n_heads * hd + 2 * d * self.n_kv * hd
                          + self.n_heads * hd * d)
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k + shared only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        moe_layers = self.n_layers - self.dense_layers
        dense_experts = moe_layers * m.n_held * 3 * self.d_model * m.d_expert
        active_experts = moe_layers * m.top_k * 3 * self.d_model * m.d_expert
        return self.param_count() - dense_experts + active_experts
