"""The program's one tracing helper: a host span whose seconds feed a counter.

``timed_span(name, obj, field)`` opens ``jax.profiler.TraceAnnotation(name)``
around a block and adds the block's host seconds to ``obj.<field>``, so that
the span in a profiler trace and the counter in a stats object come from the
same boundary. The annotation writes into the profiler's own trace when one
runs, on the device trace's clock, and costs about a microsecond when none
does. A span may cross ``await``s: it opens and closes on the event loop's
thread. Names are fixed strings (``docs/API.md``, Observability), so a trace
reduction can group by them.
"""

from __future__ import annotations

import contextlib
import time

import jax


@contextlib.contextmanager
def timed_span(name: str, obj, field: str):
    """Trace the block as ``name`` and add its host seconds to
    ``obj.<field>``."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    setattr(obj, field, getattr(obj, field) + time.perf_counter() - t0)
