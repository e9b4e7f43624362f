"""Share of the pool pages the fused decode kernel's block tables span
that it fetched, in the traced window: the engine's ``attn_pages``
counter (mapped table entries the ticks read) over its
``attn_page_slots`` counter (layers x slots x table width per tick).
A program without these counters gives nothing."""


def read(run):
    t = run.traced
    if t is None or "attn_pages" not in t.counters_close:
        return None
    spanned = t.counter("attn_page_slots")
    return 100.0 * t.counter("attn_pages") / spanned if spanned > 0 else None
