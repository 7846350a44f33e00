"""Model/shape configuration system.

Every assigned architecture is a ``ModelConfig`` (frozen dataclass). Layer
stacking is expressed as a repeating ``block`` pattern of sublayer kinds so
heterogeneous stacks (gemma2 local/global, jamba attn:mamba 1:7 with MoE on
odd layers) still scan over homogeneous parameter groups.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class LayerSpec:
    """One sublayer inside the repeating block."""

    kind: str              # "attn" | "mla" | "mamba"
    ffn: str = "mlp"       # "mlp" | "moe" | "none"
    window: int = 0        # sliding-window size; 0 = full attention


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense|moe|ssm|hybrid|encdec|vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    block: tuple[LayerSpec, ...] = ()  # () -> homogeneous full-attn + mlp
    # leading layers outside the repeating block: the block's first sublayer
    # kind with a dense MLP of width d_ff (DeepSeek's first_k_dense_replace)
    n_dense_layers: int = 0

    # attention flavour
    qk_norm: bool = False
    attn_softcap: float = 0.0
    # sequences longer than this use the chunked online-softmax path (the
    # pure-JAX twin of the flash Pallas kernel); hillclimb overrides lower it
    attn_dense_threshold: int = 8192
    # Megatron-style sequence parallelism: residual stream + norms sharded
    # over the model axis on the sequence dim; GSPMD turns the TP all-reduces
    # into reduce-scatter + all-gather pairs and elementwise traffic /= TP
    seq_parallel: bool = False
    logit_softcap: float = 0.0
    rope_theta: float = 10000.0
    mrope_sections: tuple[int, ...] = ()   # qwen2-vl M-RoPE (t, h, w) half-dims
    # YaRN rope scaling (DeepSeek-V2); factor 0 -> plain rope
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # multi-head latent attention (LayerSpec kind "mla"; no query LoRA):
    # the cache holds the normed kv_lora_rank-wide latent and one roped
    # qk_rope_head_dim-wide key per token
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    moe_experts: int = 0
    moe_topk: int = 0
    moe_d_ff: int = 0
    moe_capacity_factor: float = 1.25   # per-expert buffer = T*topk/E * this
    moe_shared_d_ff: int = 0            # shared experts as one MLP; 0 -> none
    moe_norm_topk: bool = True          # renormalise the top-k gate weights
    # "ep": experts sharded over data, token all-to-all (paper-standard);
    # "tp": expert weights sharded over model d_ff, output psum — moves
    #       T x d instead of E x C x d per layer (§Perf hillclimb)
    moe_parallel: str = "ep"

    # SSM (mamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64

    # encoder-decoder
    encoder_layers: int = 0
    encoder_seq: int = 0             # precomputed-frame length (whisper: 1500)

    # VLM stub
    vis_tokens: int = 0              # precomputed patch embeddings prepended

    act: str = "silu"                # silu | gelu
    tie_embeddings: bool = False
    embed_scale: bool = False        # multiply embeddings by sqrt(d_model) (gemma)
    norm_eps: float = 1e-6
    max_seq: int = 32768
    dtype: str = "bfloat16"
    # post-attention / post-ffn extra norms (gemma2 style)
    post_norms: bool = False

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.n_heads, 1))
        if not self.block:
            object.__setattr__(self, "block", (LayerSpec(kind="attn", ffn="mlp"),))
        assert (self.n_layers - self.n_dense_layers) % len(self.block) == 0, (
            f"{self.name}: n_layers {self.n_layers} - {self.n_dense_layers} "
            f"not divisible by block {len(self.block)}")

    @property
    def n_blocks(self) -> int:
        return (self.n_layers - self.n_dense_layers) // len(self.block)

    @property
    def stacks(self) -> tuple[tuple[str, tuple[LayerSpec, ...], int], ...]:
        """The scanned layer stacks in order, as (params key, sublayer
        pattern, repeats): the leading dense layers, then the block."""
        out = []
        if self.n_dense_layers:
            dense = dataclasses.replace(self.block[0], ffn="mlp")
            out.append(("dense", (dense,), self.n_dense_layers))
        out.append(("blocks", self.block, self.n_blocks))
        return tuple(out)

    @property
    def layer_specs(self) -> tuple[LayerSpec, ...]:
        """Every stack's sublayer pattern, in order: one entry per cache or
        pool position (``DecodeCache.layers``, the engine's pools)."""
        return tuple(spec for _, specs, _ in self.stacks for spec in specs)

    @property
    def mla_cache_width(self) -> int:
        """Lanes of an MLA cache entry: the latent, the roped key, zeros up
        to a multiple of 128 (a minor dimension of 576 makes the TPU lay the
        pool out page-minor, and the paged kernel would copy it each call)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def d_inner(self) -> int:          # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def has_attention(self) -> bool:
        return any(l.kind in ("attn", "mla") for l in self.block)

    @property
    def sub_quadratic(self) -> bool:
        """Whether the long_500k cell runs (SSM/hybrid/windowed-attention).

        Hybrids qualify: most layers are O(1)-state Mamba; the few full-
        attention layers cost O(ctx) per decoded token (linear, not
        quadratic) with a KV footprint that fits when sharded."""
        if self.family in ("ssm", "hybrid"):
            return True
        return all(l.kind == "mamba" or l.window > 0 for l in self.block)

    def _sublayer_params(self, spec: LayerSpec) -> int:
        d, hd = self.d_model, self.head_dim
        n = d                           # pre-norm
        if self.post_norms:
            n += d
        if spec.kind == "mla":
            h, r, rope = self.n_heads, self.kv_lora_rank, self.qk_rope_head_dim
            n += d * h * (self.qk_nope_head_dim + rope)          # wq
            n += d * (r + rope) + r                              # wkv_a, kv_norm
            n += r * h * (self.qk_nope_head_dim + self.v_head_dim)   # wkv_b
            n += h * self.v_head_dim * d                         # wo
        elif spec.kind == "attn":
            n += d * self.n_heads * hd          # wq
            n += 2 * d * self.n_kv_heads * hd   # wk, wv
            n += self.n_heads * hd * d          # wo
            if self.qk_norm:
                n += 2 * hd
        else:  # mamba2
            di, N, H = self.d_inner, self.ssm_state, self.ssm_heads
            n += d * (2 * di + 2 * N + H)   # in_proj (x,z,B,C,dt)
            n += self.ssm_conv * (di + 2 * N)
            n += 3 * H                       # A_log, D, dt_bias
            n += di                          # gated norm
            n += di * d                      # out_proj
        if spec.ffn == "mlp":
            n += d + 3 * d * self.d_ff
            if self.post_norms:
                n += d
        elif spec.ffn == "moe":
            n += d + d * self.moe_experts    # norm + router
            n += self.moe_experts * 3 * d * self.moe_d_ff
            n += 3 * d * self.moe_shared_d_ff
            if self.post_norms:
                n += d
        return n

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, hd = self.d_model, self.head_dim
        total = self.vocab * d                     # embed
        if not self.tie_embeddings:
            total += d * self.vocab                # lm_head
        for _, specs, count in self.stacks:
            total += count * sum(self._sublayer_params(spec) for spec in specs)
        total += d                                  # final norm
        if self.encoder_layers:
            enc = self.encoder_layers * (2 * d + d * self.n_heads * hd +
                                         2 * d * self.n_kv_heads * hd +
                                         self.n_heads * hd * d + 3 * d * self.d_ff + d)
            # cross-attention in every decoder layer
            cross = self.n_layers * (d + d * self.n_heads * hd +
                                     2 * d * self.n_kv_heads * hd + self.n_heads * hd * d)
            total += enc + cross + d
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if self.moe_experts == 0:
            return self.param_count()
        full = self.param_count()
        n_moe = sum(1 for l in self.block if l.ffn == "moe") * self.n_blocks
        inactive = n_moe * (self.moe_experts - self.moe_topk) * 3 * self.d_model * self.moe_d_ff
        return full - inactive


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str                        # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests."""
    base = dict(
        n_layers=cfg.n_dense_layers + len(cfg.block) * 2,
        d_model=64,
        n_heads=4,
        n_kv_heads=max(1, min(cfg.n_kv_heads, 2)),
        d_ff=128,
        vocab=256,
        head_dim=16,
        max_seq=128,
        encoder_layers=2 if cfg.encoder_layers else 0,
        encoder_seq=16 if cfg.encoder_layers else 0,
        vis_tokens=8 if cfg.vis_tokens else 0,
        moe_experts=min(cfg.moe_experts, 4) if cfg.moe_experts else 0,
        moe_topk=min(cfg.moe_topk, 2) if cfg.moe_topk else 0,
        moe_d_ff=32 if cfg.moe_experts else 0,
        # tiny smoke configs run drop-free so prefill+decode == forward exactly
        moe_capacity_factor=16.0 if cfg.moe_experts else 1.25,
        ssm_state=16 if cfg.ssm_state else 0,
        ssm_head_dim=16 if cfg.ssm_state else 64,
        mrope_sections=(4, 2, 2) if cfg.mrope_sections else (),
        kv_lora_rank=32 if cfg.kv_lora_rank else 0,
        qk_nope_head_dim=16 if cfg.kv_lora_rank else 0,
        qk_rope_head_dim=8 if cfg.kv_lora_rank else 0,
        v_head_dim=16 if cfg.kv_lora_rank else 0,
        moe_shared_d_ff=64 if cfg.moe_shared_d_ff else 0,
        yarn_original_max_pos=32 if cfg.yarn_factor else 0,
        dtype="float32",
    )
    base.update(overrides)
    return dataclasses.replace(cfg, **base)
