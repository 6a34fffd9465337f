"""Named host spans on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: under a
running profiler it lands in the same trace as the device's operations, so
an idle stretch of the device can be set against the span the host had
open; with no profiler running it costs about a microsecond.  The offload
path writes ``offload.submit`` (``verify``, ``plan``, ``stage``,
``launch``) and ``offload.wait`` (``fetch``); ``ServeEngine.generate_many``
writes ``serve.admit`` (meta ``slot``), ``serve.decode`` (meta
``kv_positions``: the cached positions the step's live slots attend, each
slot's position + 1; ``kv_read_positions``: those the decode kernel copies,
each slot's rounded up to whole chunks), ``serve.retire`` and
``serve.drain``.  Keep metadata to per-job spans and keep spans out of
per-element loops.
"""

from __future__ import annotations

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, **meta)
