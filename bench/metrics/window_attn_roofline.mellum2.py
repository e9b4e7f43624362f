"""The window layers' decode attention's share of its roofline: the least
time the chip needs for its work per tick (``kernels/window_attention``,
the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from
the engine's ``window_positions`` and ``tokens`` counters over the traced
window) over the device time per tick under the tick's
``window_attention`` scope.  A program without the counter or the scope
gives nothing."""
from harness import scopes


def read(run):
    t = run.traced
    if t is None or "window_positions" not in t.counters_close:
        return None
    k = run.kernel("window_attention")
    ms = scopes.scope_ms_per_tick(t.events, k.SCOPE)
    ticks = t.counter("ticks")
    if not ms or ticks <= 0:
        return None
    m = run.dims
    n_window = sum(m["window"])
    flops, nbytes = k.count(t.counter("window_positions") / ticks,
                            n_window * t.counter("tokens") / ticks,
                            m["hq"], m["hkv"], m["hd"])
    p = run.peaks
    least = max(flops / p["bf16_flops_per_s"],
                nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
