"""Configuration dataclasses for the repro framework.

One ``ModelConfig`` covers every assigned architecture family; family-specific
fields are simply unused by other families.  ``TrainConfig`` carries the
resource-aware runtime knobs that reproduce the paper's optimization chain
(①memory-efficient attention ②activation checkpointing ③gradient accumulation
④parameter sharding) plus the energy governor (§4.2).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 512
    vocab_size: int = 256
    head_dim: int = 0              # 0 -> d_model // n_heads
    max_seq_len: int = 4096

    # --- activation / norm flavour ---
    mlp_variant: str = "swiglu"    # swiglu | gelu | geglu
    norm_variant: str = "rmsnorm"  # rmsnorm | layernorm
    qkv_bias: bool = False          # qwen1.5 style
    attn_out_bias: bool = False
    qk_norm: bool = False           # gemma3 style per-head RMS on q/k
    mlp_bias: bool = False
    tie_embeddings: bool = False
    logit_softcap: float = 0.0

    # --- positional encoding ---
    rope_theta: float = 10000.0
    pos_variant: str = "rope"      # rope | mrope | learned | none
    mrope_sections: Tuple[int, ...] = (16, 24, 24)  # qwen2-vl t/h/w split of head_dim/2

    # --- attention pattern ---
    sliding_window: int = 0        # 0 -> full attention
    global_layer_every: int = 0    # hybrid: stride of full-attention layers

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01

    # --- SSM (mamba2 / hymba) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 256

    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    enc_seq_ratio: int = 4         # encoder frames = seq // ratio (conv stub downsample)

    # --- vlm ---
    n_vision_tokens: int = 0       # patch-embedding stub tokens prepended

    # --- hybrid (hymba) ---
    n_meta_tokens: int = 0         # learnable meta tokens prepended

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so the unembedding TP-shards on
        any mesh (MaxText-standard; pad logits are masked in unembed)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch can run long_500k (no full-attention S^2 term)."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (exact for our construction)."""
        from repro.param import tree_param_count
        from repro.models import registry
        return tree_param_count(registry.param_specs(self))

    def active_param_count(self) -> int:
        """MoE: params touched per token (for 6*N_active*D model flops)."""
        if self.family != "moe" or self.n_experts == 0:
            return self.param_count()
        total = self.param_count()
        # expert ffn params counted total; replace with top_k/ n_experts share
        expert_ffn = self.n_layers * self.n_experts * 3 * self.d_model * self.d_ff
        active_ffn = self.n_layers * self.top_k * 3 * self.d_model * self.d_ff
        return total - expert_ffn + active_ffn


@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False
    # axis sizes are fixed by the harness: (2,16,16) or (16,16)
    @property
    def shape(self) -> Tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclass(frozen=True)
class TrainConfig:
    # --- batch geometry ---
    global_batch: int = 8
    seq_len: int = 128
    microbatches: int = 1          # paper C2: gradient accumulation steps

    # --- optimizer ---
    learning_rate: float = 2e-4
    weight_decay: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 10
    total_steps: int = 100
    schedule: str = "cosine"       # cosine | linear | constant

    # --- dtype policy ---
    param_dtype: str = "float32"     # master weights
    compute_dtype: str = "bfloat16"  # activations / matmuls
    grad_reduce_dtype: str = ""      # "" -> compute dtype; "bfloat16" = compression

    # --- resource-aware runtime (the paper's optimization chain) ---
    attention_impl: str = "streaming"  # naive | streaming (alias: ref) |
                                       # flash (Pallas kernel)   (paper C4)
    remat_policy: str = "none"         # none | dots | full        (paper C3)
    shard_preset: str = "fsdp_tp"      # dp | fsdp | tp | fsdp_tp | fsdp_dp (C1)
    moe_dispatch_dtype: str = ""       # "" -> compute; float8_e4m3fn halves a2a
    moe_seq_chunks: int = 1            # sequence-chunked MoE (bounds expert
                                       # hidden/dispatch buffers at long seq)
    donate: bool = True
    scan_layers: bool = True
    attn_chunk: int = 512              # streaming attention KV-chunk

    # --- segment-wise parameter offload (paper C1, phone realization) ---
    offload_segments: int = 0          # 0 -> in-memory; N -> page (p,m,v) to N segment files
    offload_dir: str = ""              # "" -> <out_dir>/offload (or runs/offload)
    offload_resident: int = 2          # LRU window size in segments
    offload_prefetch: bool = True      # background double-buffered prefetch
    offload_stream_params: bool = False  # layer-streamed fwd/bwd: segments are
                                       # layer-aligned (one per block + head) and
                                       # params page through the window during
                                       # compute, not just the optimizer update
    offload_moment_dtype: str = "float32"  # float32 | bfloat16 (halves m/v segment
                                       # bytes; bf16 segment codec, fp32 math)
    offload_async_writeback: bool = True  # bounded background dirty-segment
                                       # writer: eviction no longer blocks on
                                       # encode+msync (flush/snapshot barrier)
    offload_staging: bool = True       # double-buffered host->device staging:
                                       # block i+1 converts to device arrays
                                       # while block i computes (the deferred
                                       # loss/grad-norm syncs are always on)
    base_quant: str = ""               # "" | int8: quantize the *frozen* base
                                       # segments of streamed LoRA per channel
                                       # (QLoRA-style; ~4x less flash + window)
    offload_activations: bool = False  # spill layer-boundary activations to a
                                       # per-step scratch store during the
                                       # forward sweep, re-pulled in reverse
                                       # order for backward — resident acts
                                       # stop scaling with depth (long seq)
    activation_codec: str = "fp32"     # fp32 | bf16 | int8 (per-token absmax)
                                       # storage precision of spilled acts;
                                       # fp32 is a bit-exact spill
    offload_io: str = ""               # segment read backend: "" (defer to
                                       # $REPRO_OFFLOAD_IO, else mmap) | mmap |
                                       # pread | direct (O_DIRECT) | uring |
                                       # auto (probe uring -> direct -> pread);
                                       # all backends are bit-identical

    # --- LoRA (paper C6) ---
    lora_rank: int = 0                 # 0 -> Full-FT
    lora_alpha: float = 32.0
    lora_dropout: float = 0.0
    lora_targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo")

    # --- energy governor (paper C5) ---
    energy_check_every: int = 1        # K
    energy_threshold: float = 0.60     # mu (battery fraction)
    energy_reduction: float = 0.50     # rho

    # --- fault tolerance ---
    checkpoint_every: int = 0          # 0 -> disabled
    checkpoint_dir: str = ""
    keep_checkpoints: int = 3

    # --- profiling ---
    profile_dir: str = ""              # "" -> off; else a jax.profiler trace
    profile_steps: Tuple[int, int] = (0, 0)  # of steps first..last (inclusive)

    @property
    def micro_batch(self) -> int:
        assert self.global_batch % self.microbatches == 0
        return self.global_batch // self.microbatches


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    train: TrainConfig = field(default_factory=TrainConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    seed: int = 0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16,
            "float8_e4m3fn": jnp.float8_e4m3fn}[name]


# ----------------------------------------------------------------------------
# Input shape suites assigned by the harness (per-arch cells).
# ----------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k":    ShapeSpec("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeSpec("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeSpec("long_500k",   524_288, 1,   "decode"),
}


def cells_for(cfg: ModelConfig):
    """The (shape) cells that apply to an architecture.

    long_500k requires sub-quadratic attention (prompt rule) — skipped for
    pure full-attention archs and recorded as such in the roofline table.
    """
    out = []
    for name, spec in SHAPES.items():
        if name == "long_500k" and not cfg.sub_quadratic:
            out.append((name, "SKIP(full-attention)"))
        else:
            out.append((name, "RUN"))
    return out
