"""Model configuration covering all 10 assigned architectures."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads

    # block wiring
    block_type: str = "llama"   # llama | parallel (cohere)
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm | nonparametric_ln
    mlp_type: str = "swiglu"    # swiglu | gelu (whisper)
    use_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0

    # attention kind: "gqa", or "mla" (DeepSeek-V2 multi-head latent
    # attention: the cache holds a normalised latent of kv_lora_rank values
    # and one roped key of qk_rope_head_dim a token, shared by all heads)
    attention: str = "gqa"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # YaRN rotary scaling (DeepSeek-V2); yarn_factor 0 = plain RoPE
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    # softmax scale factor 0.1 * yarn_mscale * ln(yarn_factor) + 1, squared
    # (DeepSeek-V2's mscale_all_dim; its rotary factor mscale /
    # mscale_all_dim is 1 for the published equal values and not applied)
    yarn_mscale: float = 1.0

    # MoE.  n_experts is the number of experts this chip holds: experts
    # [expert_offset, expert_offset + n_experts) of the router's
    # n_routed_experts (0 = all of them are held here)
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    n_routed_experts: int = 0
    expert_offset: int = 0
    moe_d_ff: int = 0           # routed/shared expert width (0 -> d_ff)
    n_shared_experts: int = 0   # one SwiGLU of n_shared * moe_d_ff
    norm_topk_prob: bool = True  # renormalise the top-k gates
    # False: GShard grouped dispatch with capacity (tokens may drop);
    # True: every token reaches each held expert it is routed to
    moe_dropless: bool = False
    n_dense_layers: int = 0     # leading layers with a dense SwiGLU of d_ff

    # hybrid (RecurrentGemma): blocks cycle [recurrent]*rec_per_attn + [attn]
    rglru: bool = False
    rec_per_attn: int = 2
    window: int = 0             # local-attention window (0 = full)
    conv_width: int = 4
    lru_width: int = 0          # 0 -> d_model

    # attention-free linear recurrence (RWKV-6 "Finch")
    rwkv: bool = False

    # encoder-decoder (Whisper): n_layers = decoder layers
    encoder_layers: int = 0
    n_frames: int = 1500        # audio frontend stub sequence length
    max_decode_len: int = 32768  # learned decoder position table size

    # VLM (LLaVA-NeXT): precomputed patch embeddings prepended to text
    n_image_tokens: int = 0

    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True

    def __post_init__(self):
        if self.attention not in ("gqa", "mla"):
            raise ValueError(f"unknown attention kind {self.attention!r}")
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.rglru and self.lru_width == 0:
            object.__setattr__(self, "lru_width", self.d_model)
        if self.n_heads and not self.rwkv:
            assert self.n_heads % max(self.n_kv_heads, 1) == 0, \
                "q heads must be divisible by kv heads (GQA)"

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def router_width(self) -> int:
        return self.n_routed_experts or self.n_experts

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers if self.is_moe else 0

    # ------------------------------------------------------------------
    def attention_params(self) -> int:
        """Weights of one self-attention block."""
        d = self.d_model
        if self.attention == "mla":
            h, r = self.n_heads, self.qk_rope_head_dim
            qk = self.qk_nope_head_dim + r
            return (d * h * qk + d * (self.kv_lora_rank + r)
                    + self.kv_lora_rank
                    + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                               + self.v_head_dim)
                    + h * self.v_head_dim * d)
        n_q = self.n_heads * self.head_dim
        n_kv = self.n_kv_heads * self.head_dim if not self.rwkv else 0
        return d * n_q + 2 * d * n_kv + n_q * d

    def moe_params(self) -> int:
        """Weights of one expert layer as held here: the router at its
        full width, the held experts and the shared experts."""
        d, f = self.d_model, self.expert_d_ff
        return (d * self.router_width + self.n_experts * 3 * d * f
                + 3 * d * self.n_shared_experts * f)

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, ff = self.d_model, self.d_ff
        attn = self.attention_params()
        if self.rwkv:
            # time-mix (r,k,v,g,o + decay LoRA) + channel-mix
            attn = 5 * d * d
            mlp = 3 * d * ff
        elif self.is_moe:
            mlp = self.moe_params()
        else:
            mlp = 3 * d * ff
        per_layer = attn + mlp + 2 * d
        total = self.n_layers * per_layer
        if self.n_dense_layers:
            total += self.n_dense_layers * (3 * d * ff - mlp)
        if self.rglru:
            w = self.lru_width
            rec_block = d * w * 2 + w * self.conv_width + 3 * w + w * d + 3 * d * ff
            n_attn = self.n_layers // (self.rec_per_attn + 1)
            n_rec = self.n_layers - n_attn
            total = n_rec * rec_block + n_attn * per_layer
        if self.is_encdec:
            enc = self.encoder_layers * (attn + 3 * d * ff + 2 * d)
            dec_cross = self.n_layers * attn
            total += enc + dec_cross
        total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return int(total)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k of the router's
        experts, as many of them held here as the share holds)."""
        if not self.is_moe:
            return self.param_count()
        routed = 3 * self.d_model * self.expert_d_ff
        held = self.n_moe_layers * self.n_experts * routed
        per_token = self.top_k * self.n_experts / self.router_width
        return int(self.param_count() - held
                   + self.n_moe_layers * per_token * routed)
