"""Typed configuration system for the repro framework.

Everything in the framework is driven by three dataclasses:

* :class:`ModelConfig`    -- architecture definition (one per assigned arch).
* :class:`ThinKVConfig`   -- the paper's compression hyper-parameters (Sec. 6.1).
* :class:`MeshConfig`     -- parallelism layout.

plus :class:`TrainConfig` / :class:`ServeConfig` wrappers used by the
launchers.  Configs are plain frozen dataclasses so they hash, compare and
print cleanly, and can be used as static args to ``jax.jit``.
"""
from __future__ import annotations

import dataclasses
import enum
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple


class ArchFamily(str, enum.Enum):
    """Model family; drives which model builder is used."""

    DENSE = "dense"          # decoder-only dense transformer
    MOE = "moe"              # decoder-only transformer with MoE FFN
    VLM = "vlm"              # vision frontend (stub) + decoder-only LM
    ENCDEC = "encdec"        # encoder-decoder (whisper)
    SSM = "ssm"              # attention-free state-space model (mamba1)
    HYBRID = "hybrid"        # mamba2 backbone + shared attention blocks


class PositionEmbedding(str, enum.Enum):
    ROPE = "rope"
    SINUSOIDAL = "sinusoidal"
    LEARNED = "learned"
    NONE = "none"


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8                  # the router's width (published)
    num_experts_per_token: int = 2
    capacity_factor: float = 1.25
    # token group size for the one-hot dispatch einsum (GShard-style);
    # bounds the quadratic dispatch cost to O(tokens * group * d).
    dispatch_group: int = 256
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.01
    # experts this device holds, ``(first, stop)``; () holds all.  The
    # router still spans every expert; tokens routed to experts held
    # elsewhere add nothing here (``layers.moe.moe_held``).
    held: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.held:
            a, b = self.held
            if not 0 <= a < b <= self.num_experts:
                raise ValueError(f"held experts {self.held} outside "
                                 f"[0, {self.num_experts})")

    @property
    def held_range(self) -> Tuple[int, int]:
        return tuple(self.held) if self.held else (0, self.num_experts)

    @property
    def num_held(self) -> int:
        a, b = self.held_range
        return b - a


@dataclass(frozen=True)
class RopeConfig:
    """Rotary embedding of one layer kind: plain (``kind="default"``) or
    YaRN (``"yarn"``: per-frequency blend of interpolated and
    extrapolated inverse frequencies, cos/sin scaled by
    ``attention_factor``; Hugging Face's ``_compute_yarn_parameters``)."""

    theta: float = 1e4
    kind: str = "default"
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.kind not in ("default", "yarn"):
            raise ValueError(f"unknown rope kind {self.kind!r}")


#: Layer kinds of :attr:`ModelConfig.layer_kinds`.
FULL, WINDOW = "full", "window"


@dataclass(frozen=True)
class SSMConfig:
    state_size: int = 16          # N  (mamba1: 16, mamba2: 64+)
    conv_width: int = 4
    expand: int = 2               # d_inner = expand * d_model
    dt_rank: int = 0              # 0 -> ceil(d_model/16)  (mamba1)
    head_dim: int = 64            # mamba2 only
    ngroups: int = 1              # mamba2 only
    chunk_size: int = 128         # mamba2 chunked scan


@dataclass(frozen=True)
class ModelConfig:
    """Architecture definition.

    Sizes follow the assignment table verbatim (see README).  ``head_dim`` is
    derived as ``d_model // num_heads`` unless given explicitly.
    """

    name: str
    family: ArchFamily
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                     # 0 -> d_model // num_heads
    qkv_bias: bool = False                # qwen2
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1e4
    position_embedding: PositionEmbedding = PositionEmbedding.ROPE
    sliding_window: int = 0               # window of WINDOW layers
    # per-layer attention kind (FULL / WINDOW), from Hugging Face's
    # ``layer_types``; () is every layer FULL.  A window layer's query at
    # position p attends p - sliding_window + 1 .. p.
    layer_kinds: Tuple[str, ...] = ()
    # rope of FULL layers; None is plain rope at ``rope_theta``, which
    # WINDOW layers always use
    rope_full: Optional[RopeConfig] = None
    # per-head RMSNorm of q and k before rope (Qwen3-style decoder layer)
    qk_norm: bool = False
    act: str = "silu"                     # mlp activation ("silu"|"gelu")
    mlp_gated: bool = True                # SwiGLU vs plain MLP
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # -- hybrid (zamba2): a shared attention block is invoked after every
    #    ``hybrid_attn_every`` backbone layers.  0 disables.
    hybrid_attn_every: int = 0
    # -- enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 1500               # whisper: 30s of audio frames
    cross_attention: bool = False
    # -- vlm (paligemma): number of stub image-patch tokens prepended
    num_image_tokens: int = 0
    frontend_dim: int = 0                 # stub frontend embedding width
    # -- numerics
    dtype: str = "bfloat16"
    # -- logit softcap (gemma-style), 0 disables
    logit_softcap: float = 0.0

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.layer_kinds:
            if len(self.layer_kinds) != self.num_layers:
                raise ValueError(f"{len(self.layer_kinds)} layer kinds for "
                                 f"{self.num_layers} layers")
            bad = set(self.layer_kinds) - {FULL, WINDOW}
            if bad:
                raise ValueError(f"unknown layer kinds {sorted(bad)}")
            if WINDOW in self.layer_kinds and self.sliding_window <= 0:
                raise ValueError("window layers need sliding_window > 0")

    # ---- layer kinds --------------------------------------------------------
    def kind_of(self, layer: int) -> str:
        return self.layer_kinds[layer] if self.layer_kinds else FULL

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_layers)
                     if self.kind_of(i) == WINDOW)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_layers)
                     if self.kind_of(i) == FULL)

    def rope_of(self, kind: str) -> RopeConfig:
        if kind == FULL and self.rope_full is not None:
            return self.rope_full
        return RopeConfig(theta=self.rope_theta)

    # ---- derived quantities -------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def attention_free(self) -> bool:
        return self.family == ArchFamily.SSM

    def num_attention_layers(self) -> int:
        """Number of layer-invocations that own a KV cache."""
        if self.family == ArchFamily.SSM:
            return 0
        if self.family == ArchFamily.HYBRID:
            return self.num_layers // max(self.hybrid_attn_every, 1)
        if self.family == ArchFamily.ENCDEC:
            return self.num_layers          # decoder self-attn layers
        return self.num_layers

    def param_count(self) -> int:
        """Analytic parameter count (used for roofline MODEL_FLOPS=6ND)."""
        d, h, kv, hd, ff, v = (self.d_model, self.num_heads,
                               self.num_kv_heads, self.head_dim,
                               self.d_ff, self.vocab_size)
        emb = v * d * (1 if self.tie_embeddings else 2)
        attn = d * h * hd + 2 * d * kv * hd + h * hd * d
        if self.qkv_bias:
            attn += (h + 2 * kv) * hd
        mlp = d * ff * (3 if self.mlp_gated else 2)
        if self.moe is not None:
            mlp = mlp * self.moe.num_experts + d * self.moe.num_experts
        per_layer = attn + mlp + 2 * d
        total = emb + self.num_layers * per_layer
        if self.family == ArchFamily.SSM:
            di = self.ssm.expand * d
            n = self.ssm.state_size
            dt_rank = self.ssm.dt_rank or -(-d // 16)
            per = (d * 2 * di + di * self.ssm.conv_width
                   + di * (dt_rank + 2 * n) + dt_rank * di + di + di * d + 2 * d)
            total = emb + self.num_layers * per
        if self.family == ArchFamily.HYBRID:
            di = self.ssm.expand * d
            n = self.ssm.state_size
            nh = di // self.ssm.head_dim
            per = (d * (2 * di + 2 * self.ssm.ngroups * n + nh) +
                   di * self.ssm.conv_width + di + nh + di * d + 2 * d)
            mlp_full = d * ff * 3
            shared = (attn + mlp_full + 2 * d)  # one shared block
            total = emb + self.num_layers * per + shared
        if self.family == ArchFamily.ENCDEC:
            # add encoder stack + cross attention in decoder
            enc_per = attn + mlp + 2 * d
            cross = d * h * hd + 2 * d * kv * hd + h * hd * d + d
            total += self.encoder_layers * enc_per + self.num_layers * cross
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k experts count)."""
        if self.moe is None:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        e, k = self.moe.num_experts, self.moe.num_experts_per_token
        dense_expert = d * ff * 3
        inactive = self.num_layers * dense_expert * (e - k)
        return int(self.param_count() - inactive)

    def kv_bytes_per_token_fullkv(self) -> int:
        """bf16 K+V bytes per generated token (all cached layers)."""
        return 2 * 2 * self.kv_dim * self.num_attention_layers()


# ---------------------------------------------------------------------------
# ThinKV configuration (paper Sec. 6.1 defaults)
# ---------------------------------------------------------------------------

class ThoughtType(enum.IntEnum):
    """Thought categories.  Integer order == importance order rho (Sec. 3.2):
    TRANSITION(0) < EXECUTION(1) < REASONING(2)."""

    TRANSITION = 0
    EXECUTION = 1
    REASONING = 2


@dataclass(frozen=True)
class ThinKVConfig:
    enabled: bool = True
    num_thoughts: int = 3                         # |T|
    refresh_interval: int = 128                   # tau
    group_size: int = 16                          # g
    block_size: int = 16                          # CT block (TPU tile-aligned; paper: 8)
    token_budget: int = 1024                      # k
    retention_schedule: Tuple[int, ...] = (64, 32, 16, 8, 4)   # R
    min_retention: int = 4
    # precision per thought type, bits, indexed by ThoughtType value.
    # Paper practice: R4 E4 T2 ("R tokens maintain comparable accuracy even
    # at 4-bit"); R8 available via precision=(2,4,8).
    precision: Tuple[int, int, int] = (2, 4, 4)   # (T, E, R)
    # sparsity thresholds theta (calibrated; defaults from synthetic calib)
    sparsity_thresholds: Tuple[float, float] = (0.55, 0.80)
    num_calib_layers: int = 4                     # |L*|
    kmeans_iters: int = 8
    max_segments: int = 512                       # >= max_gen / tau
    # cross-attention caches (whisper): TBQ only, never evicted
    quantize_cross_attention: bool = True

    @property
    def max_blocks_per_seq_factor(self) -> float:
        """Physical blocks per sequence ~ budget/block_size with slack for
        the in-flight unquantized group + per-segment minimums."""
        return 1.5

    def avg_bits(self, thought_mix=(0.15, 0.45, 0.40)) -> float:
        """Average KV precision given a (T, E, R) thought mix."""
        t, e, r = thought_mix
        pt, pe, pr = self.precision
        return t * pt + e * pe + r * pr


@dataclass(frozen=True)
class MeshConfig:
    """Parallelism layout.  axis_names/shape must multiply to #devices."""

    shape: Tuple[int, ...] = (16, 16)
    axis_names: Tuple[str, ...] = ("data", "model")

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.axis_names

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def axis_size(self, name: str) -> int:
        if name not in self.axis_names:
            return 1
        return self.shape[self.axis_names.index(name)]

    @property
    def dp_degree(self) -> int:
        return self.axis_size("data") * self.axis_size("pod")


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    warmup_steps: int = 100
    decay_steps: int = 10000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


@dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig
    mesh: MeshConfig = MeshConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    seq_len: int = 4096
    global_batch: int = 256
    microbatches: int = 1                 # grad accumulation steps
    remat: str = "full"                   # "none"|"full"|"dots"
    steps: int = 100
    seed: int = 0
    # fault tolerance
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    # distributed optimization
    grad_compression: str = "none"        # "none"|"int8_ef"
    pipeline_stages: int = 0              # >0: GPipe over 'pod' axis


@dataclass(frozen=True)
class ServeConfig:
    model: ModelConfig
    thinkv: ThinKVConfig = ThinKVConfig()
    mesh: MeshConfig = MeshConfig()
    max_seqs: int = 32                    # request slots (continuous batching)
    prefill_len: int = 128
    max_gen_len: int = 1024
    kv_seq_len: int = 0                   # decode shapes: existing cache length
    temperature: float = 0.0
    top_p: float = 1.0                    # nucleus mass; 1.0 disables
    seed: int = 0


# ---------------------------------------------------------------------------
# Input shapes assigned to every architecture
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                             # "train" | "prefill" | "decode"


SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def shape_cells(arch_cfg: ModelConfig):
    """The (shape) cells defined for an architecture (all 4 per assignment;
    long_500k for full-attention archs runs in the ThinKV budget-bound
    configuration -- see DESIGN.md Sec. 4)."""
    return [SHAPES[s] for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k")]


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    kw: Dict[str, Any] = dict(
        num_layers=2,
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads else 0,
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        name=cfg.name + "-smoke",
    )
    if cfg.moe is not None:
        kw["moe"] = replace(
            cfg.moe, num_experts=max(4, 2 * cfg.moe.num_experts_per_token),
            dispatch_group=64, held=())
    if cfg.layer_kinds:
        # one whole period of the kinds, with a window tests reach
        period = cfg.layer_kinds.index(FULL) + 1
        kw["num_layers"] = period
        kw["layer_kinds"] = cfg.layer_kinds[:period]
        kw["sliding_window"] = min(cfg.sliding_window, 16)
    if cfg.ssm is not None:
        kw["ssm"] = replace(cfg.ssm, state_size=min(cfg.ssm.state_size, 16),
                            head_dim=16, chunk_size=16)
    if cfg.family == ArchFamily.ENCDEC:
        kw["encoder_layers"] = 2
        kw["encoder_seq"] = 16
    if cfg.family == ArchFamily.HYBRID:
        kw["hybrid_attn_every"] = 2
    if cfg.family == ArchFamily.VLM:
        kw["num_image_tokens"] = 4
        kw["frontend_dim"] = 32
    if cfg.family == ArchFamily.SSM:
        kw["num_heads"] = 0
        kw["num_kv_heads"] = 0
        kw["d_ff"] = 0
    kw.update(overrides)
    return replace(cfg, **kw)


def config_to_dict(cfg) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)
