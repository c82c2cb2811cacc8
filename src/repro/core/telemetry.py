"""The program's tracing: host spans, device scopes and process counters.

* ``span(name, **meta)`` is ``jax.profiler.TraceAnnotation``: a host event
  in the profiler's trace, on the same clock as the device events beside
  it, with ``meta`` as its stats.  With no profiler session active it costs
  under a microsecond, so spans stay in the code unconditionally.
* ``scope(name)`` is ``jax.named_scope``: the operations traced inside it
  carry the name in their HLO ``op_name`` metadata.  It changes neither the
  program's numerics nor its device time.
* ``counters()``: counts since this module was imported, process-wide:

  - ``compiles``: JAX's backend-compile events (a persistent-cache hit
    records one too);
  - ``device_gets``: ``jax.device_get`` calls made while
    ``counting_device_gets`` is active;
  - ``chunk_traces``: traces of a chunk function (``rounds.ChunkStep``
    misses its trace cache);
  - ``chunk_step_reuses``: ``rounds.make_chunk_step`` calls that get the
    traced programs of an earlier call with an equal chunk function key.

Every span and scope of the program is named ``fzoo.<phase>``:

=========================  =====  ==============================================
name                       kind   covers
=========================  =====  ==============================================
fzoo.simulate              span   one ``algorithms.simulate`` call
fzoo.init                  span   the RFF draw and the clients' initial states
fzoo.eval_x0               span   F(x_0) and the history buffers
fzoo.chunk                 span   one chunk-step call (one dispatch)
fzoo.chunk_step.trace      span   tracing the chunk function
fzoo.chunk_step.compile    span   the first call of a new jitted chunk step
fzoo.boundary              span   the chunk boundary after each chunk
fzoo.local                 scope  the T local steps (``.update``, ``.score``,
                                  ``.grad`` inside it)
fzoo.aggregate             scope  the server means of x and of w
fzoo.round_end             scope  round-end active queries and the w fit
fzoo.eval                  scope  F(x_{r+1}) inside the round
=========================  =====  ==============================================
"""

from __future__ import annotations

import contextlib
import threading

import jax

span = jax.profiler.TraceAnnotation
scope = jax.named_scope

#: Monitoring event JAX records once per backend executable compile.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_counts = {"compiles": 0, "device_gets": 0, "chunk_traces": 0, "chunk_step_reuses": 0}
_get_depth = 0
_real_device_get = jax.device_get


def count(name: str) -> None:
    """Add one to counter ``name``."""
    with _lock:
        _counts[name] += 1


def counters() -> dict:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counts)


def _on_event_duration(event: str, duration: float, **kw) -> None:
    del duration, kw
    if event == COMPILE_EVENT:
        count("compiles")


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def _counted_device_get(x):
    count("device_gets")
    return _real_device_get(x)


@contextlib.contextmanager
def counting_device_gets():
    """While active (nesting allowed), each ``jax.device_get`` call adds one
    to ``device_gets``; ``jax.device_get`` is restored on exit."""
    global _get_depth, _real_device_get
    with _lock:
        if _get_depth == 0:
            _real_device_get = jax.device_get
            jax.device_get = _counted_device_get
        _get_depth += 1
    try:
        yield
    finally:
        with _lock:
            _get_depth -= 1
            if _get_depth == 0:
                jax.device_get = _real_device_get
