"""Names of the serving path's host spans and device scopes, and the
helpers that open them.

HOST SPANS are ``jax.profiler.TraceAnnotation`` intervals: the profiler
writes them into its host planes on the same clock as the device planes,
and with no profiler session running they cost about a microsecond and
record nothing, so they are always on.  Names use ``/`` so that they
never collide with dotted names a caller may add around the engine.  A
span that crosses an ``await`` is an interval on the event loop's
thread, not a stack frame: coroutines that run meanwhile fall inside it.

DEVICE SCOPES name the phases of the compiled decode tick.  :func:`scope`
opens a ``jax.named_scope`` (the phase's path lands in every operation's
HLO ``op_name`` metadata, which profiler tools show) and sets the phase's
name as the ``scope`` frontend attribute, which the device trace keeps
in each operation's event name (``frontend_attributes={scope="advance"}``).
The parts of cache maintenance inside ``advance`` are named scopes only:
distinct attribute values inside the maintenance scan change what the
TPU compiler emits (about 1% more instructions in the tick), while one
value per phase leaves the compiled tick as it is without them.  Both
are metadata: the traced computation, its primitives and operands are
unchanged.

Span tree of one serve-loop iteration (``Orchestrator.serve``)::

    serve/step (step_num = the tick it dispatches)
      serve/dispatch
        engine/headroom     commit headroom (and the trip cap when packed)
        engine/launch       input transfers + the jitted tick call
      serve/wait            waits for the tick's result (executor thread:)
        engine/fetch_tokens   tokens, validity and flags
        engine/fetch_logits   the [R, V] logits
      engine/consume
      serve/deliver         token fan-out to the request streams
      serve/admit           admission sweep
        engine/prefill      one request's prefill (``arrival`` metadata)

``engine/sync`` wraps every blocking device-to-host read the engine makes
outside the result fetch; each is counted in ``metrics["host_syncs"]``.

Scope tree of the tick (``tick`` / ``mega``)::

    tick_core/{trunk, attention, sparsity_probe, residual, advance, unembed}
    tick_core/trunk/moe             the held experts (models with experts)
    tick_core/window_attention      the rings (models with window layers)
    tick_core/advance/.../{gather_view, commit_evict, refresh, sync_tables}
    sample

``moe`` and ``window_attention`` are phases too (each sets the attribute)
but only a model with experts or window layers has them; a model without
either compiles the tick it had before they existed.
"""
from __future__ import annotations

import contextlib

import jax
from jax.experimental.xla_metadata import set_xla_metadata

# ---- host spans ----------------------------------------------------------
STEP = "serve/step"
DISPATCH = "serve/dispatch"
WAIT = "serve/wait"
DELIVER = "serve/deliver"
ADMIT = "serve/admit"
HEADROOM = "engine/headroom"
LAUNCH = "engine/launch"
FETCH_TOKENS = "engine/fetch_tokens"
FETCH_LOGITS = "engine/fetch_logits"
CONSUME = "engine/consume"
PREFILL = "engine/prefill"
SYNC = "engine/sync"

SPANS = (STEP, DISPATCH, WAIT, DELIVER, ADMIT, HEADROOM, LAUNCH,
         FETCH_TOKENS, FETCH_LOGITS, CONSUME, PREFILL, SYNC)

# ---- device scopes -------------------------------------------------------
TICK_CORE = "tick_core"
TRUNK = "trunk"
ATTENTION = "attention"
PROBE = "sparsity_probe"
RESIDUAL = "residual"
ADVANCE = "advance"
UNEMBED = "unembed"
SAMPLE = "sample"
#: the phases, each also named by the ``scope`` frontend attribute
PHASES = (TICK_CORE, TRUNK, ATTENTION, PROBE, RESIDUAL, ADVANCE, UNEMBED,
          SAMPLE)

#: phases only a model with window layers or experts has
WINDOW_ATTENTION = "window_attention"
MOE = "moe"

GATHER_VIEW = "gather_view"
COMMIT_EVICT = "commit_evict"
REFRESH = "refresh"
SYNC_TABLES = "sync_tables"
#: the parts of ``engine_advance``, named scopes only
ADVANCE_PARTS = (GATHER_VIEW, COMMIT_EVICT, REFRESH, SYNC_TABLES)

SCOPES = PHASES + ADVANCE_PARTS

#: The frontend attribute that carries a phase's name into the HLO.
SCOPE_ATTR = "scope"

span = jax.profiler.TraceAnnotation


@contextlib.contextmanager
def scope(name: str):
    """Name a phase of the traced tick: ``jax.named_scope(name)`` plus
    the ``scope`` frontend attribute set to ``name``."""
    with jax.named_scope(name), set_xla_metadata(**{SCOPE_ATTR: name}):
        yield
