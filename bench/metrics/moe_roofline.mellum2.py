"""The held-expert layers' share of their roofline: the least time the
chip needs for their work per tick (``kernels/held_experts``, the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the engine's
``experts_hit``, ``expert_assignments`` and ``tokens`` counters over the
traced window) over the device time per tick under the tick's ``moe``
scope.  A program without the counters or the scope gives nothing."""
from harness import scopes


def read(run):
    t = run.traced
    if t is None or "expert_assignments" not in t.counters_close:
        return None
    k = run.kernel("held_experts")
    ms = scopes.scope_ms_per_tick(t.events, k.SCOPE)
    ticks = t.counter("ticks")
    if not ms or ticks <= 0:
        return None
    m = run.dims
    flops, nbytes = k.count(t.counter("experts_hit") / ticks,
                            t.counter("expert_assignments") / ticks,
                            t.counter("tokens") / ticks, m["L"], m["d"],
                            m["ff"], m["E"])
    p = run.peaks
    least = max(flops / p["bf16_flops_per_s"],
                nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
