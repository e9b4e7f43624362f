"""mellum2-12b-a2.5b  [moe]  28L d_model=2304 32H (GQA kv=4) hd=128,
64 experts of width 896, top-8, vocab 98304; layers "window, window,
window, full" repeated, window 1024; plain rope (theta 5e5) in window
layers, YaRN (factor 16 over 8192) in full layers
[hf: JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

Every layer's MLP is sparse (``mlp_layer_types``), so ``d_ff`` is the
expert width (the config's dense ``intermediate_size`` 7168 is unused).
q and k are RMS-normed per head (the Qwen3-MoE decoder layer whose keys
the config carries).
"""
from repro.config import (FULL, WINDOW, ArchFamily, ModelConfig, MoEConfig,
                          RopeConfig)

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family=ArchFamily.MOE,
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=896,
    vocab_size=98304,
    norm_eps=1e-6,
    rope_theta=5e5,
    sliding_window=1024,
    layer_kinds=(WINDOW, WINDOW, WINDOW, FULL) * 7,
    rope_full=RopeConfig(theta=5e5, kind="yarn", factor=16.0,
                         original_max_position=8192, beta_fast=32.0,
                         beta_slow=1.0,
                         attention_factor=1.2772588722239782),
    qk_norm=True,
    act="silu",
    mlp_gated=True,
    moe=MoEConfig(num_experts=64, num_experts_per_token=8),
)
