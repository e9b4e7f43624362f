"""``ct_paged_attention_fused``'s share of its roofline: the least time
the chip needs for the kernel's work (``kernels/ct_paged_attention_fused``,
the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s) over
the kernel's summed device time in the trace.  The work per tick is taken
from the cache state at the trace's start and end, averaged."""
from harness import trace
from harness.context import TICK_PROGRAM as TICK


def read(run):
    t = run.traced
    if t is None:
        return None
    k = run.kernel("ct_paged_attention_fused")
    secs = trace.seconds_of(t.events, trace.OPS_LINE, k.PATTERN)
    ticks = run.module_count(TICK)
    if secs <= 0 or ticks <= 0:
        return None
    f0, b0 = k.count(t.snap_open, run.dims["hq"])
    f1, b1 = k.count(t.snap_close, run.dims["hq"])
    p = run.peaks
    least = ticks * max((f0 + f1) / 2 / p["bf16_flops_per_s"],
                        (b0 + b1) / 2 / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
