"""Model / shape configuration schema for every assigned architecture."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp


# sublayer kinds; a layer is a tuple of sublayers, a period a tuple of layers
ATTN, MAMBA, XATTN = "attn", "mamba", "xattn"
MLA = "mla"                      # multi-head latent attention (DeepSeek-V2)
MLP, MOE = "mlp", "moe"


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0              # 0 -> d_model // n_heads

    # repeating period: tuple of layers, each a tuple of sublayer kinds,
    # e.g. jamba: (("mamba","moe"), ("mamba","mlp"), ..., ("attn","moe"), ...).
    # empty -> every layer is ("attn", "mlp"/"moe").
    period: tuple = ()

    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    d_ff_expert: int = 0
    capacity_factor: float | None = 1.25  # an expert keeps ceil(N k / E x
    #                              factor) of a call's N rows; None: dropless,
    #                              every routed (row, expert) pair is computed
    moe_tp: bool = False         # experts < |model| axis: shard d_ff instead
    moe_impl: str = "psum"       # "psum" (tokens replicated over model) |
    #                              "a2a" (GLSU-style token all-to-all EP)
    norm_topk_prob: bool = True  # False: gates are the softmax over all
    #                              experts, read at the top k (DeepSeek-V2)
    n_shared_experts: int = 0    # always-on experts, run as one SwiGLU of
    #                              n_shared_experts * d_ff_expert
    first_dense: int = 0         # first_k_dense_replace: leading layers with
    #                              a dense MLP of d_ff, outside the period

    # multi-head latent attention (kv_lora_rank > 0): keys and values come
    # from a normalised latent of kv_lora_rank plus one roped key shared by
    # every head, which is what the cache holds
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # attention
    rope_theta: float = 1e4
    window: int | None = None    # sliding-window attention
    # YaRN context extension (rope_scaling type "yarn"); factor 1 is plain
    # rotary embedding
    yarn_factor: float = 1.0
    yarn_original_max: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # SSM (mamba2 / jamba)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv: int = 4

    # enc-dec
    n_enc_layers: int = 0
    # vlm / audio frontend stub
    n_ctx_tokens: int = 0        # image patches / audio frames per sample
    d_ctx: int = 0               # frontend embedding dim (projected to d_model)

    # numerics / training
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    remat: bool = True
    unroll_layers: bool = False  # python-loop periods (cost-analysis variants)
    loss_chunk: int = 0          # chunked cross-entropy (0 = single shot)

    # shape-cell applicability: {shape_name: reason} for noted skips
    skip_shapes: Any = dataclasses.field(default_factory=dict)

    # ---------------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding/head tables padded to a 256 multiple so the vocab dim
        shards over any mesh axis (mamba2's 50280, seamless' 256206...).
        Logits for padded ids are masked to -inf in the loss/decode paths."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def attn_kind(self) -> str:
        return MLA if self.kv_lora_rank else ATTN

    @property
    def layer_period(self) -> tuple:
        if self.period:
            return self.period
        return ((self.attn_kind, MOE if self.n_experts else MLP),)

    @property
    def lead_period(self) -> tuple:
        """The layer kinds of the ``first_dense`` leading layers."""
        return ((self.attn_kind, MLP),)

    @property
    def n_periods(self) -> int:
        p = len(self.layer_period)
        n = self.n_layers - self.first_dense
        assert n % p == 0, (self.name, n, p)
        return n // p

    @property
    def qk_head_dim(self) -> int:
        """Query/key width per head under latent attention."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values cached per token and latent-attention layer: the
        normalised latent and the roped shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def d_inner_ssm(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner_ssm // self.ssm_head_dim

    def _sublayer_params(self, kind: str) -> int:
        d, hd = self.d_model, self.head_dim
        if kind in (ATTN, XATTN):
            return (d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                    + self.n_heads * hd * d + d)
        if kind == MAMBA:
            di = self.d_inner_ssm
            H, N = self.n_ssm_heads, self.ssm_state
            return (d * (2 * di + 2 * N + H) + self.ssm_conv * (di + 2 * N)
                    + 3 * H + di + di * d + d)
        if kind == MLA:
            H, r = self.n_heads, self.kv_lora_rank
            return (d * H * self.qk_head_dim + d * self.latent_dim + r
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * d + d)
        if kind == MLP:
            return 3 * d * self.d_ff + d
        if kind == MOE:
            ffe = self.d_ff_expert or self.d_ff
            return (d * self.n_experts + self.n_experts * 3 * d * ffe
                    + 3 * d * self.n_shared_experts * ffe + d)
        raise ValueError(kind)

    def n_params(self) -> int:
        """Total parameter count (embedding included)."""
        d = self.d_model
        n = self.vocab_size * d                       # embed
        if not self.tie_embeddings:
            n += d * self.vocab_size                  # head
        for layer in self.layer_period:
            for kind in layer:
                n += self.n_periods * self._sublayer_params(kind)
        for layer in self.lead_period:
            for kind in layer:
                n += self.first_dense * self._sublayer_params(kind)
        n += d                                        # final norm
        if self.family == "encdec":
            n += self.n_enc_layers * (self._sublayer_params(ATTN)
                                      + self._sublayer_params(MLP)) + d
        if self.d_ctx:
            n += self.d_ctx * d                       # frontend projection
        return n

    def n_active_params(self) -> int:
        """Active (per-token) parameters — MoE counts top-k experts only."""
        if not self.n_experts:
            return self.n_params()
        ffe = self.d_ff_expert or self.d_ff
        n_moe = sum(1 for layer in self.layer_period
                    for k in layer if k == MOE) * self.n_periods
        inactive = n_moe * (self.n_experts - self.experts_per_token) \
            * 3 * self.d_model * ffe
        return self.n_params() - inactive

    def runnable(self, shape_name: str) -> bool:
        return shape_name not in self.skip_shapes
