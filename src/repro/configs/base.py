"""Configuration dataclasses for architectures, input shapes, and hardware.

Every assigned architecture is expressed as a ``ModelConfig``.  The same
dataclass also describes the *reduced* smoke-test variants (``reduce()``),
so tests and the dry-run share one definition of each model family.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "rwkv", "hybrid", "encoder", "vlm")


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters (one instance per assigned arch)."""

    name: str
    family: str                      # one of FAMILIES
    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    # --- attention ---------------------------------------------------------
    n_heads: int = 0                 # 0 for attention-free families
    n_kv_heads: int = 0
    head_dim: int = 0                # explicit (qwen3-style); 0 => d_model//n_heads
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0          # 0 => full attention; >0 => every layer
                                     # windowed over a ring cache (mixtral)
    # per-layer attention kinds (mellum): "sliding_attention" layers attend
    # over the last ``layer_window`` positions, "full_attention" layers over
    # all of them; every layer keeps a full-length cache.  () => all alike.
    layer_types: Tuple[str, ...] = ()
    layer_window: int = 0
    # the full-attention layers' YaRN RoPE: (factor, original max positions,
    # beta_fast, beta_slow, attention_factor); () => default RoPE everywhere
    rope_yarn: Tuple[float, ...] = ()
    causal: bool = True              # False for encoder-only
    mlp_type: str = "swiglu"         # "swiglu" (3 mats) | "gelu" (2 mats)
    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0
    top_k: int = 0
    moe_dense_residual: bool = False  # Arctic: dense FFN residual in parallel
    dense_residual_ff: int = 0        # width of Arctic's parallel dense FFN
    capacity_factor: float = 1.25
    # the experts whose weights live here: ids [expert_offset, expert_offset
    # + experts_held) of the router's n_experts (0 => all of them)
    experts_held: int = 0
    expert_offset: int = 0
    # --- SSM / RWKV --------------------------------------------------------
    ssm_state: int = 0               # Mamba2 state size per head
    ssm_head_dim: int = 64           # Mamba2 P (head channel dim)
    rwkv_head_dim: int = 64          # RWKV6 head size
    attention_every: int = 0         # zamba2: shared attn block cadence (layers)
    # --- IO ----------------------------------------------------------------
    input_mode: str = "tokens"       # "tokens" | "embeds" (modality-frontend stub)
    tie_embeddings: bool = False
    # --- numerics / execution ---------------------------------------------
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    # --- capability flags (drive shape-grid skips; see DESIGN.md §4) -------
    supports_decode: bool = True     # False for encoder-only
    subquadratic: bool = False       # True => runs long_500k
    # --- distribution defaults (overridable by the launcher) ---------------
    remat: bool = True
    scan_layers: bool = True
    scan_group: int = 0          # layers per remat group (0 = auto ≈ √L)
    seq_parallel: bool = False   # Megatron-SP activations (§Perf iteration)
    use_pallas: bool = False     # Pallas kernels for attention/scan hot-spots
                                 # (TPU target; interpret=True on CPU)

    def __post_init__(self):
        if self.layer_types:
            if len(self.layer_types) != self.n_layers:
                raise ValueError(f"{self.name}: {len(self.layer_types)} layer types "
                                 f"for {self.n_layers} layers")
            bad = set(self.layer_types) - {"sliding_attention", "full_attention"}
            if bad:
                raise ValueError(f"{self.name}: unknown layer types {sorted(bad)}")
            if "sliding_attention" in self.layer_types and self.layer_window <= 0:
                raise ValueError(f"{self.name}: sliding layers need layer_window > 0")
            if self.sliding_window:
                raise ValueError(f"{self.name}: per-layer windows keep full caches; "
                                 "the ring window (sliding_window) is another path")
        if self.expert_offset + self.n_experts_held > self.n_experts:
            raise ValueError(f"{self.name}: experts [{self.expert_offset}, "
                             f"{self.expert_offset + self.n_experts_held}) not among "
                             f"{self.n_experts}")

    # -- derived ------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.n_heads:
            return self.d_model // self.n_heads
        return 0

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.resolved_head_dim

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_experts_held(self) -> int:
        """Experts whose weights this model holds (the router spans all
        ``n_experts``)."""
        return self.experts_held or self.n_experts

    @property
    def layer_windows(self) -> Tuple[int, ...]:
        """Each layer's attention window from ``layer_types`` (0 = full)."""
        return tuple(self.layer_window if t == "sliding_attention" else 0
                     for t in self.layer_types)

    @property
    def has_layer_kinds(self) -> bool:
        """Layers differ in window or RoPE, so the layer loop carries each
        layer's kind beside its weights."""
        return any(self.layer_windows) or bool(self.layer_types and self.rope_yarn)

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-FLOPs and sanity)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.family == "encoder":
            emb = v * d  # output head only; inputs are embeds
        mlp_mats = 3 if self.mlp_type == "swiglu" else 2
        per_layer = 0
        if self.family in ("dense", "moe", "encoder", "vlm"):
            attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
            if self.is_moe:
                # the held experts' weights, and the router over all of them
                ffn = self.n_experts_held * 3 * d * f + d * self.n_experts
                if self.moe_dense_residual:
                    ffn += 3 * d * (self.dense_residual_ff or f)
            else:
                ffn = mlp_mats * d * f
            per_layer = attn + ffn + 2 * d
        elif self.family == "rwkv":
            # time-mix (r,k,v,g,o + decay lora) + channel-mix (k,v,r)
            per_layer = 5 * d * d + 2 * d * 64 + (d * f + f * d + d * d) + 4 * d
        elif self.family == "hybrid":
            # mamba2 block: in_proj -> [z, x, B, C, dt], conv, out_proj
            d_inner = 2 * d
            H = d_inner // self.ssm_head_dim
            per_layer = d * (2 * d_inner + 2 * self.ssm_state + H)
            per_layer += 4 * (d_inner + 2 * self.ssm_state)   # conv
            per_layer += d_inner * d + d_inner
        n = emb + self.n_layers * per_layer + d
        if self.attention_every:
            # one shared attention + MLP block (zamba2, weights shared)
            n += d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
            n += 3 * d * f + 2 * d
        return n

    def active_param_count(self) -> int:
        """Activated params per token (MoE: the top_k of n_experts routed
        picks, of which a held share lands here on average)."""
        if not self.is_moe:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        total = self.param_count()
        held = self.n_experts_held
        active = self.top_k * held // self.n_experts
        inactive = self.n_layers * (held - active) * 3 * d * f
        return total - inactive

    def reduce(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests.  Per-layer
        attention kinds keep their first four layers (a whole 3:1 period)."""
        n_layers = min(self.n_layers,
                       4 if (self.attention_every or self.layer_types) else 2)
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=n_layers,
            layer_types=self.layer_types[:n_layers],
            layer_window=min(self.layer_window, 16),
            experts_held=0,
            expert_offset=0,
            d_model=128,
            d_ff=256,
            vocab_size=512,
            n_heads=min(self.n_heads, 4) if self.n_heads else 0,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads else 0,
            head_dim=32 if self.n_heads else 0,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            dense_residual_ff=128 if self.moe_dense_residual else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=32,
            rwkv_head_dim=32,
            attention_every=2 if self.attention_every else 0,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            dtype="float32",
        )


# ---------------------------------------------------------------------------
# Input shapes (the four assigned shape cells)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


TRAIN_4K = InputShape("train_4k", "train", 4_096, 256)
PREFILL_32K = InputShape("prefill_32k", "prefill", 32_768, 32)
DECODE_32K = InputShape("decode_32k", "decode", 32_768, 128)
LONG_500K = InputShape("long_500k", "decode", 524_288, 1)

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


def shape_grid(cfg: ModelConfig) -> Tuple[InputShape, ...]:
    """The runnable shape cells for an arch (DESIGN.md §4 skip rules)."""
    shapes = [TRAIN_4K, PREFILL_32K]
    if cfg.supports_decode:
        shapes.append(DECODE_32K)
        if cfg.subquadratic:
            shapes.append(LONG_500K)
    return tuple(shapes)


# ---------------------------------------------------------------------------
# Hardware tiers (roofline constants + orchestrator cost signals)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HardwareTier:
    """A pool tier: the 'hardware' leg of the paper's DU triplet."""

    name: str
    peak_flops: float        # bf16 FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    ici_bw: float            # bytes/s per link
    hbm_bytes: float         # HBM capacity per chip
    cost_per_chip_hour: float


# Target hardware for the dry-run / roofline (per the task statement).
TPU_V5E = HardwareTier(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    hbm_bytes=16e9,
    cost_per_chip_hour=1.20,
)

# Additional tiers used only by the orchestrator simulator to model a
# heterogeneous fleet (public on-demand list prices; perf from public specs).
TPU_V4 = HardwareTier("tpu-v4", 275e12, 1228e9, 50e9, 32e9, 3.22)
TPU_V5P = HardwareTier("tpu-v5p", 459e12, 2765e9, 100e9, 95e9, 4.20)
TPU_V6E = HardwareTier("tpu-v6e", 918e12, 1640e9, 100e9, 32e9, 2.70)

TIERS = {t.name: t for t in (TPU_V5E, TPU_V4, TPU_V5P, TPU_V6E)}
