"""Model configuration covering every assigned architecture family.

One frozen dataclass describes dense / MoE / SSM / hybrid / enc-dec / VLM
stacks plus the paper's LRAM & PKM memory-layer insertions, so a single
generic transformer assembly (repro.models.transformer) serves all ten
assigned architectures and the paper's own BERT-style model.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.lram import LRAMConfig
from repro.core.pkm import PKMConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None       # default: d_model // num_heads

    # attention
    attention: str = "full"              # full | swa | mla
    window: int = 4096                   # SWA window
    qkv_bias: bool = False
    rope_theta: float = 1e4
    pos_scheme: str = "rope"             # rope | mrope | learned | none
    mrope_sections: tuple[int, ...] = (16, 24, 24)  # qwen2-vl t/h/w split
    attn_chunk: int = 2048               # kv/q chunking threshold (flash-style)
    attn_impl: str = "auto"              # auto | dense | chunked
    # latent attention (mla, DeepSeek-V2 §2.1): keys and values expanded
    # from an RMS-normed latent of kv_lora_rank; each head's query/key is
    # qk_nope_head_dim wide plus a qk_rope_head_dim RoPE part (the key's
    # shared by all heads), its value v_head_dim wide; no query LoRA
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # blocks
    norm: str = "rms"                    # rms | layer
    rms_norm_eps: float = 1e-6           # RMS norms' eps (layer norms: nn's)
    act: str = "swiglu"                  # swiglu | gelu
    mlp_bias: bool = False
    tie_embeddings: bool = False

    # MoE: dropless top-k routing over all num_experts; this device holds
    # experts [first, first + experts_held) of each expert layer (all by
    # default) and adds only their part, plus the shared experts
    num_experts: int = 0
    top_k_experts: int = 2
    experts_held: Optional[int] = None   # default: num_experts
    moe_intermediate_size: Optional[int] = None  # expert width; default d_ff
    n_shared_experts: int = 0            # one shared SwiGLU, n x expert width
    first_k_dense_replace: int = 0       # leading layers with a dense MLP
    scoring_func: str = "softmax"        # softmax | sigmoid (+ bias state)
    routed_scaling_factor: float = 1.0   # gates scaled after renormalising
    router_aux_weight: float = 0.01      # balance loss weight (alpha)

    # SSM (mamba2 / zamba2)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    ssm_chunk: int = 64
    ssm_conv: int = 4
    hybrid_pattern: int = 0              # zamba2: shared attn every N mamba blocks
    shared_attention: bool = False

    # enc-dec (whisper): frontend is a STUB — input_specs feeds embeddings
    encoder_layers: int = 0
    encoder_len: int = 1500

    # vlm (qwen2-vl): vision frontend is a STUB — input_specs feeds embeddings
    vision_tokens: int = 0

    # memory layers (the paper's technique, first-class)
    lram_layers: tuple[int, ...] = ()
    lram: Optional[LRAMConfig] = None
    pkm_layers: tuple[int, ...] = ()
    pkm: Optional[PKMConfig] = None

    # objective / numerics
    objective: str = "clm"               # clm | mlm
    max_seq: int = 8192                  # for learned positions only
    dtype: str = "float32"
    remat: bool = True
    scan_layers: bool = True

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_experts)
        if self.moe_intermediate_size is None:
            object.__setattr__(self, "moe_intermediate_size", self.d_ff)
        assert self.experts_held <= self.num_experts
        assert self.scoring_func in ("softmax", "sigmoid")
        assert self.num_heads % max(1, self.num_kv_heads) == 0
        if self.lram_layers:
            assert self.lram is not None
        if self.pkm_layers:
            assert self.pkm is not None

    # ---- derived -----------------------------------------------------------

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def moe_layer(self, i: int) -> bool:
        """Whether layer i's FFN is the expert layer (the first
        `first_k_dense_replace` layers keep a dense MLP)."""
        return self.num_experts > 0 and i >= self.first_k_dense_replace

    def attn_params(self) -> int:
        d, h = self.d_model, self.num_heads
        if self.attention == "mla":
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            r = self.kv_lora_rank
            return (d * h * qk + d * (r + self.qk_rope_head_dim) + r
                    + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)
        hd = self.head_dim
        return 2 * h * hd * d + 2 * self.num_kv_heads * hd * d

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks), for 6ND roofline;
        an expert layer counts the experts held here."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        attn = self.attn_params()
        per = 3 if self.act == "swiglu" else 2
        mlp = per * d * f
        fe = self.moe_intermediate_size
        moe = (self.experts_held * per * d * fe + d * self.num_experts
               + self.n_shared_experts * per * d * fe)
        n = 0
        for i in range(self.num_layers):
            if self.family == "ssm":
                n += self._mamba_params()
                continue
            if self.family == "hybrid":
                n += self._mamba_params()
                continue
            n += attn
            if i in self.lram_layers and self.lram is not None:
                n += self.lram.num_params + d * d + 4 * d * d
            elif i in self.pkm_layers and self.pkm is not None:
                n += self.pkm.num_params
            elif self.moe_layer(i):
                n += moe
            else:
                n += mlp
        if self.family == "hybrid" and self.hybrid_pattern:
            n += attn + mlp  # one shared block
        if self.family == "encdec":
            n += self.encoder_layers * (attn + mlp) + self.num_layers * attn
        n += v * d * (1 if self.tie_embeddings else 2)
        return n

    def _mamba_params(self) -> int:
        d, di = self.d_model, self.d_inner
        n_bc = 2 * self.ssm_groups * self.ssm_state
        return (
            d * (2 * di + n_bc + self.ssm_heads)  # in_proj (z,x,B,C,dt)
            + self.ssm_conv * (di + n_bc)         # conv1d
            + 3 * self.ssm_heads                  # A, D, dt_bias
            + di                                  # gate norm
            + di * d                              # out_proj
        )

    def active_param_count(self) -> int:
        """Active params per token (MoE counts only top-k experts)."""
        if self.num_experts == 0:
            return self.param_count()
        per = 3 if self.act == "swiglu" else 2
        inactive = ((self.experts_held - self.top_k_experts) * per
                    * self.d_model * self.moe_intermediate_size)
        moe_layers = sum(self.moe_layer(i) and i not in self.lram_layers
                         and i not in self.pkm_layers
                         for i in range(self.num_layers))
        return self.param_count() - moe_layers * inactive


def validate_cell(cfg: ModelConfig, shape_name: str) -> Optional[str]:
    """Return a skip-reason if (arch x shape) is not runnable, else None."""
    if shape_name.startswith("long"):
        sub_quadratic = (
            cfg.family in ("ssm", "hybrid") or cfg.attention == "swa"
        )
        if not sub_quadratic:
            return (
                "long_500k needs sub-quadratic attention; "
                f"{cfg.name} is pure full-attention (see DESIGN.md §5)"
            )
    return None
