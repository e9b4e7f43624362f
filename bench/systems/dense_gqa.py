"""The system under test for dense GQA configurations: ``ThinKVEngine``
built from a configuration file, on the compiled kernel backend.

Everything here is glue from the file's Hugging Face keys and its
``engine`` block to the program's own config types.
"""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.config import ArchFamily, ModelConfig
    hq = cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family=ArchFamily.DENSE,
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=hq, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // hq),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=bool(cfg["assumed"]["attention_bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]), act=cfg["hidden_act"],
        mlp_gated=True, dtype=cfg["torch_dtype"])


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
    """The engine's cache state for the kernel counts, copied to the host
    (``harness.cachestate.summarize``)."""
    from harness.cachestate import summarize
    from repro.core import ct_cache as CC
    c, view = eng.caches, eng.pool.view
    return summarize(c.slot_state, c.slot_bits, c.buf_len,
                     [not s.free for s in eng.scheduler.slots],
                     int(CC.VALID), int(view.k_codes.shape[2]),
                     int(view.k_codes.shape[-1]),
                     int(view.k_scales.shape[-1]))


def at_close(eng) -> tuple:
    """The cache metadata as the last dispatched step left it, and the
    request each occupied slot serves: ``(arrays, {slot: request})``.
    Taken at the window's close; the arrays stay on the device (nothing
    waits here) until :func:`held` copies them."""
    c = eng.caches
    return ((c.slot_state, c.slot_pos, c.buf_len, c.num_tokens),
            {s.idx: s.request for s in eng.scheduler.slots if not s.free})


def held(arrays) -> dict:
    """:func:`at_close`'s arrays on the host: per slot and layer which
    cache positions are held (``held`` ``[R, L, NS]`` and their token
    positions ``pos``), the buffer length and the tokens the cache has
    taken in."""
    import numpy as np
    from repro.core import ct_cache as CC
    state, pos, buf_len, num_tokens = (np.asarray(a) for a in arrays)
    return {"held": state == int(CC.VALID), "pos": pos,
            "buf_len": buf_len, "num_tokens": num_tokens}
