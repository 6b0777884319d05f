"""Program spans for the profiler's trace, and the op each belongs to.

``span(name)`` marks one phase of a request. With tracing off (the
default) it returns one shared no-op context manager after a single flag
check. With tracing on (``enable(True)``) it opens a
``jax.profiler.TraceAnnotation(name, op=<id>)``, which lands on the
profiler's host plane, one line per thread, on the device trace's clock;
the name reads back bare, with ``op`` as an event stat.

A request's spans share an op id: the outermost span on a thread that
has none (or ``op()``) allocates one, and ``carry(fn)`` hands the current
one to a job that runs on another thread. Parentage on a thread is span
nesting; across threads it is the shared op.

``ON`` is also the switch for the time counters the plane's objects keep
(``RpcFabric.pickle_s``, ``OffloadFS.lease_s``): they accrue only while
tracing is on.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools

# every span name the program emits
SPANS = (
    "serve.attach", "serve.decode",
    "kvstore.put", "kvstore.put.pack", "kvstore.put.layout",
    "kvstore.put.wire", "kvstore.put.commit",
    "kvstore.fetch", "kvstore.fetch.plan", "kvstore.fetch.wire",
    "kvstore.fetch.assemble", "kvstore.fetch.unpack",
    "lsm.scan", "lsm.scan.plan", "lsm.scan.wire", "lsm.scan.merge",
    "fabric.batch", "pushdown.read", "pushdown.eval",
)

ON = False
_annotation = None  # jax.profiler.TraceAnnotation, bound by enable(True)
_op = contextvars.ContextVar("repro_obs_op", default=None)
_op_ids = itertools.count(1)
_NOOP = contextlib.nullcontext()


def enable(on: bool) -> None:
    global ON, _annotation
    if on:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    ON = bool(on)


def span(name: str):
    """``with span("kvstore.put"):`` — a no-op unless tracing is on."""
    return _scoped(name) if ON else _NOOP


def op():
    """An op id for the block, if the thread has none, with no span."""
    return _scoped(None) if ON else _NOOP


@contextlib.contextmanager
def _scoped(name):
    token = _op.set(next(_op_ids)) if _op.get() is None else None
    try:
        if name is None:
            yield
        else:
            with _annotation(name, op=_op.get()):
                yield
    finally:
        if token is not None:
            _op.reset(token)


def carry(fn):
    """``fn`` bound to the current op, to run on another thread."""
    if not ON:
        return fn
    op_id = _op.get()

    def run(*args, **kwargs):
        token = _op.set(op_id)
        try:
            return fn(*args, **kwargs)
        finally:
            _op.reset(token)

    return run
