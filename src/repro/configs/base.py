"""Config system: model / shape / parallelism / SpAMM dataclasses + registry.

Every assigned architecture registers a `ModelConfig` in its own module under
`repro.configs`; `get_config(name)` resolves it. Shape cells (train_4k,
prefill_32k, decode_32k, long_500k) are global and paired with every arch.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# SpAMM feature config (the paper's technique as a first-class switch)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SpammConfig:
    enable: bool = False
    tau: float = 0.0                    # norm-product threshold (paper τ)
    valid_ratio: Optional[float] = None # alternative: target executed fraction
    tile: int = 64                      # LoNum
    block_n: int = 1                    # super-column width in the mm kernel
    backend: str = "auto"               # pallas | interpret | jnp | auto
    bwd: str = "dense"                  # dense | spamm gradient path
    levels: int = 0                     # norm-pyramid coarsening steps for
                                        # hierarchical gating (0 = flat); the
                                        # coarsest gate runs at coarse_tile
    dtype: str = "float32"              # GEMM compute dtype: float32 | bf16 |
                                        # int8 (f32 accumulate always; gating
                                        # stays conservative via widened τ —
                                        # see repro.kernels.quantize)
    moe_bmm: bool = False               # inference-only: run MoE grouped FFNs
                                        # through the batched spamm_bmm path
                                        # (per-expert weight plans; grads flow
                                        # through the gated product, so keep
                                        # False for bwd="dense" training)
    autotune: bool = False              # roofline-autotune block_n/levels/
                                        # bucket per weight at freeze time
                                        # (core.cost); block_n/levels above
                                        # become the tuner's defaults (always
                                        # in its search space)
    tune_profile: Optional[str] = None  # path to a calibrated cost-profile
                                        # JSON (benchmarks/autotune.py
                                        # --calibrate); None = nominal
                                        # per-backend coefficients

    @property
    def coarse_tile(self) -> int:
        """Tile size of the coarsest pyramid level (== tile when flat)."""
        return self.tile * (2 ** self.levels)


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    expert_ff: int
    num_shared: int = 0
    shared_ff: int = 0
    impl: str = "tp"                    # "tp": ff-dim TP; "ep": expert-parallel
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001    # load-balancing aux loss
    # routing (DeepSeek-V3's "noaux_tc": sigmoid scores, a correction bias
    # that only selects, normalised top-k weights times a routed scale);
    # every config normalises its top-k weights
    scoring: str = "softmax"            # softmax | sigmoid
    score_bias: bool = False            # seeded e_score_correction_bias
    routed_scale: float = 1.0
    shared_gate: bool = True            # sigmoid gate on the shared expert
    held: int = 0                       # experts this chip holds, ids
                                        # [0, held); 0 = all of them

    @property
    def held_experts(self) -> int:
        return self.held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:                         # multi-head latent attention
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Cached floats per token and layer: the latent and the shared
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:                         # Mamba2 / SSD
    state: int = 128
    head_dim: int = 64
    expand: int = 2
    chunk: int = 256
    conv_dim: int = 4
    n_groups: int = 1


@dataclass(frozen=True)
class RGLRUConfig:                       # RecurrentGemma
    lru_width: int = 0                  # 0 → d_model
    conv_dim: int = 4
    c_exponent: float = 8.0
    block_pattern: Tuple[str, ...] = ("rec", "rec", "attn")  # 1 attn : 2 rec


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                         # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                   # 0 → d_model // num_heads
    act: str = "silu"                   # silu (SwiGLU) | gelu (SwiGLU-gelu) | gelu_mlp
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None  # SWA window (mixtral, local attn)
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    mla: Optional[MLAConfig] = None     # latent attention in place of MHA
    first_dense: int = 0                # leading layers with a dense d_ff
                                        # MLP before the MoE layers
    frontend: Optional[str] = None      # None | "vision_stub" | "audio_stub"
    subquadratic: bool = False          # eligible for long_500k
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        kw = dict(
            num_layers=2,
            d_model=64,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 4) if self.num_kv_heads else 0,
            d_ff=128,
            vocab=256,
            head_dim=16,
        )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 8),
                expert_ff=32,
                shared_ff=64 if self.moe.num_shared else 0,
                top_k=min(self.moe.top_k, 2),
            )
            if self.moe.held:
                # a chip's share: 8 of 16 experts held, top-3 over all 16
                kw["moe"] = dataclasses.replace(
                    kw["moe"], num_experts=min(self.moe.num_experts, 16),
                    held=min(self.moe.held, 8),
                    top_k=min(self.moe.top_k, 3))
        if self.mla is not None:
            kw["mla"] = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                                  qk_rope_head_dim=8, v_head_dim=16)
        if self.first_dense:
            kw["first_dense"] = 1
            kw["num_layers"] = 3       # the dense layer and two MoE layers
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(self.ssm, state=16, head_dim=16, chunk=32)
        if self.rglru is not None:
            kw["rglru"] = dataclasses.replace(self.rglru, lru_width=64)
            kw["num_layers"] = 3  # one full (rec, rec, attn) group
        if self.sliding_window:
            kw["sliding_window"] = 32
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# shape cells (assigned; identical for all 10 archs)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


# ---------------------------------------------------------------------------
# parallelism / runtime
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParallelConfig:
    fsdp: bool = True                   # ZeRO-3 param sharding over data axis
    remat: str = "full"                 # none | dots | full
    scan_layers: bool = True
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    loss_chunk: int = 1024              # chunked-CE seq chunk
    attn_q_chunk: int = 512             # flash q block
    attn_kv_chunk: int = 1024           # flash kv block
    decode_seq_shard: bool = True       # seq-sharded KV decode over model axis
    seq_shard_acts: bool = False        # Megatron-SP: residual stream sharded
                                        # on seq over model (psum → RS+AG)
    grad_compression: str = "none"      # none | int8_ef


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    seed: int = 0
    ckpt_every: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

ARCH_IDS = (
    "llava-next-mistral-7b",
    "mamba2-1.3b",
    "starcoder2-7b",
    "granite-34b",
    "codeqwen1.5-7b",
    "qwen2.5-32b",
    "recurrentgemma-9b",
    "qwen2-moe-a2.7b",
    "mixtral-8x22b",
    "musicgen-large",
)

# every config the zoo builds: the dry-run's ten, and the configs the chip
# benchmark serves beyond them
ZOO_IDS = ARCH_IDS + ("moonlight-16b-a3b",)

# archs for which long_500k runs (sub-quadratic sequence mixing); the rest
# record a documented skip (see DESIGN.md §6).
LONG_CONTEXT_ARCHS = ("mamba2-1.3b", "recurrentgemma-9b", "mixtral-8x22b")


def get_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{name.replace('-', '_').replace('.', '_')}")
    return mod.CONFIG


def cells(include_skipped: bool = False):
    """All (arch, shape) dry-run cells. 37 runnable + 3 documented skips."""
    out = []
    for arch in ARCH_IDS:
        for shape in SHAPES.values():
            skipped = shape.name == "long_500k" and arch not in LONG_CONTEXT_ARCHS
            if skipped and not include_skipped:
                continue
            out.append((arch, shape.name, skipped))
    return out
