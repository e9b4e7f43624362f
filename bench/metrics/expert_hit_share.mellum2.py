"""Share of the held experts that received at least one token, per layer
and tick, in the traced window: the engine's ``experts_hit`` counter over
its ``expert_slots`` counter (layers x held experts x ticks).  A program
without these counters gives nothing."""


def read(run):
    t = run.traced
    if t is None or "expert_slots" not in t.counters_close:
        return None
    slots = t.counter("expert_slots")
    return 100.0 * t.counter("experts_hit") / slots if slots > 0 else None
