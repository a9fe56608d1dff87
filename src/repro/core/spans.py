"""Host spans at the boundaries of the program's layers.

Each span is a ``jax.profiler.TraceAnnotation`` with one of the constant
names below, ``repro/<layer>.<stage>``. It records into the profiler's
trace, on the clock of the device planes, only while a profiler runs
(``jax.profiler.trace``); otherwise it costs a fraction of a microsecond.
``docs/session.md`` lists what each span covers; the benchmark's
per-layer metrics (``perfbench/metrics/``) read them by name.
"""

from __future__ import annotations

import functools

import jax

PREFIX = "repro/"

#: ``Database.put``, whole: array wrap, host-tier check, catalog entry.
SESSION_PUT = PREFIX + "session.put"
#: the catalog's statistics refresh (``relation.measure_stats``).
SESSION_STATS = PREFIX + "session.stats"
#: ``QueryHandle.step`` / ``grad``, whole: program, environment,
#: statistics snapshot, executable lookup, the call, donation marks.
SESSION_STEP = PREFIX + "session.step"
#: the session's executable lookup: engine, ``lower`` and
#: ``compile_auto`` caches (lowering and planning nest inside on a miss).
ENGINE_LOOKUP = PREFIX + "engine.lookup"
#: ``RAEngine.lower`` on a cache miss: validate, rewrite, abstract walk.
ENGINE_LOWER = PREFIX + "engine.lower"
#: ``Lowered.compile`` on a cache miss: plans, shardings, ``jax.jit``.
ENGINE_PLAN = PREFIX + "engine.plan"
#: ``Compiled.__call__``, whole: signature check, padding, reshard
#: accounting and placement, the dispatch.
ENGINE_CALL = PREFIX + "engine.call"
#: the jitted step's call: JAX's dispatch (the XLA compile on a first call).
ENGINE_DISPATCH = PREFIX + "engine.dispatch"

NAMES = (
    SESSION_PUT, SESSION_STATS, SESSION_STEP, ENGINE_LOOKUP, ENGINE_LOWER,
    ENGINE_PLAN, ENGINE_CALL, ENGINE_DISPATCH,
)

#: ``with span(NAME):`` around part of a function.
span = jax.profiler.TraceAnnotation


def spanned(name: str):
    """Decorator: every call of the function inside the span ``name``."""
    return functools.partial(jax.profiler.annotate_function, name=name)
