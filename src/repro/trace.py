"""Host spans on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``.  While no
profiler trace is being taken it is an inactive TraceMe and costs about
a microsecond; under ``jax.profiler.trace`` it lands on the trace's host
plane, one line per thread, on the same clock as the device planes, so
each stretch in which the device ran nothing lines up with what each
host thread was doing.

Names are ``<layer>.<what>`` and belong to one thread each:

* serving loop (``engine-worker``): ``sched.round``, ``sched.admit``,
  ``sched.plan_chunk``, ``sched.book``, ``kv.write_back_wait``,
  ``kv.restore_wait``, ``kv.flight_wait``, ``kv.page_import``,
  ``fabric.get``, ``exec.dispatch``, ``exec.sync``, ``loop.idle``;
* client thread: ``router.route``;
* fetch-ahead worker (``skymem-fetch``): ``restore.decode``,
  ``write_back.blocks`` and inside it ``write_back.resume``,
  ``write_back.forward``, ``write_back.encode``, ``fabric.set``.

A span that belongs to one request passes ``rid=<request_id>``, so that
all of a request's spans share one identifier.  Counters of the same
boundaries are ``EngineStats`` fields; this module keeps nothing.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """A host span named ``name`` carrying ``meta`` as its arguments."""
    return TraceAnnotation(name, **meta)
