"""The system under test for configurations with window and full
attention layers and routed experts held in part on this chip:
``ThinKVEngine`` built from a configuration file, on the compiled kernel
backend.  Importing this module fails at once, with the reason, in a
program that has no layer kinds.

Everything here is glue from the file's Hugging Face keys, its
``held_experts`` range and its ``engine`` block to the program's own
config types.  ``num_experts`` is the count held here; the router's
width is the published count, the first number of ``reduced``'s
``num_experts``.
"""
from __future__ import annotations

try:
    from repro.config import FULL, WINDOW, RopeConfig
except ImportError as e:  # a program older than layer kinds
    raise SystemExit(f"window_moe: this program has no window layers or "
                     f"per-kind rope ({e}); it cannot serve this "
                     f"configuration") from None

WINDOW_TYPE = "sliding_attention"


def model_config(cfg: dict):
    from repro.config import ArchFamily, ModelConfig, MoEConfig
    L = cfg["num_hidden_layers"]
    ropes = cfg["rope_parameters"]
    full = ropes["full_attention"]
    e0, e1 = cfg["held_experts"]
    if not cfg["norm_topk_prob"] or ropes["sliding_attention"]["rope_type"] \
            != "default":
        raise ValueError(f"{cfg['name']}: the engine renormalises the top-k "
                         f"gates and gives window layers plain rope")
    return ModelConfig(
        name=cfg["name"], family=ArchFamily.MOE, num_layers=L,
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=bool(cfg["attention_bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(ropes["sliding_attention"]["rope_theta"]),
        sliding_window=cfg["sliding_window"],
        layer_kinds=tuple(WINDOW if t == WINDOW_TYPE else FULL
                          for t in cfg["layer_types"][:L]),
        rope_full=RopeConfig(
            theta=float(full["rope_theta"]), kind=full["rope_type"],
            factor=float(full["factor"]),
            original_max_position=int(
                full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        qk_norm=bool(cfg["assumed"]["qk_norm"]), act=cfg["hidden_act"],
        mlp_gated=True, dtype=cfg["torch_dtype"],
        moe=MoEConfig(num_experts=cfg["reduced"]["num_experts"][0],
                      num_experts_per_token=cfg["num_experts_per_tok"],
                      held=(e0, e1)))


def build_engine(cfg: dict, weights: dict, seed: int, temperature: float,
                 top_p: float):
    """``ThinKVEngine`` over the benchmark's weights, kernel backend."""
    from repro.config import ServeConfig, ThinKVConfig
    from repro.serving.engine import ThinKVEngine
    e = cfg["engine"]
    tk = ThinKVConfig(group_size=e["group_size"], block_size=e["block_size"],
                      refresh_interval=e["refresh_interval"],
                      token_budget=e["token_budget"],
                      precision=tuple(e["precision"]))
    scfg = ServeConfig(model=model_config(cfg), thinkv=tk,
                       max_seqs=e["max_seqs"], temperature=temperature,
                       top_p=top_p, seed=seed & 0x7FFFFFFF)
    return ThinKVEngine(scfg, params=weights, backend="kernel")


def cache_snapshot(eng) -> dict:
    """The pool's cache state (full layers) for the kernel counts, copied
    to the host (``harness.cachestate.summarize``)."""
    from harness.cachestate import summarize
    from repro.core import ct_cache as CC
    c, view = eng.caches, eng.pool.view
    return summarize(c.slot_state, c.slot_bits, c.buf_len,
                     [not s.free for s in eng.scheduler.slots],
                     int(CC.VALID), int(view.k_codes.shape[2]),
                     int(view.k_codes.shape[-1]),
                     int(view.k_scales.shape[-1]))


def at_close(eng) -> tuple:
    """The pool's cache metadata as the last dispatched step left it, and
    the request each occupied slot serves: ``(arrays, {slot: request})``.
    The arrays stay on the device until :func:`held` copies them."""
    c = eng.caches
    return ((c.slot_state, c.slot_pos, c.buf_len, c.num_tokens),
            {s.idx: s.request for s in eng.scheduler.slots if not s.free})


def held(arrays) -> dict:
    """:func:`at_close`'s arrays on the host: per slot and FULL layer
    which cache positions are held (``held`` ``[R, L_full, NS]`` and
    their token positions ``pos``), the buffer length and the tokens the
    cache has taken in.  Window layers hold a fixed window, which the
    reference works out itself."""
    import numpy as np
    from repro.core import ct_cache as CC
    state, pos, buf_len, num_tokens = (np.asarray(a) for a in arrays)
    return {"held": state == int(CC.VALID), "pos": pos,
            "buf_len": buf_len, "num_tokens": num_tokens}
