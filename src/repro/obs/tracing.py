"""Kernel/phase tracing: named scopes for jit traces and on-demand
profiler captures.

``kernel_scope`` is what the kernel wrappers in :mod:`repro.kernels.ops`
enter around their bodies: under an active ``jax.profiler.trace()``
capture (or any XLA dump) the scatter / gather / fold phases then show up
as named regions instead of anonymous fusions.  ``jax.named_scope`` adds
trace-time metadata only — no ops, no retraces, zero runtime cost — and
is skipped entirely when telemetry is disabled.

``annotation`` is the host-side counterpart (``TraceAnnotation``): a
span around a host region (the engine loop's partition-stats copy, its
dispatches, its sync) on the profiler's own clock, so a capture puts it
beside the device's ops.  Its fields reach ``perfetto_trace.json.gz`` as
the event's ``args``.  With telemetry disabled it is a no-op.
"""
from __future__ import annotations

import contextlib

import jax

from . import metrics

_NULL = contextlib.nullcontext()


def kernel_scope(name: str):
    """``jax.named_scope(name)`` when telemetry is enabled, else a
    no-op context.  Safe inside jit traces and shard_map bodies."""
    if not metrics.enabled():
        return _NULL
    return jax.named_scope(name)


class _NullSpan:
    """What :func:`annotation` returns with telemetry disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **meta):
        pass


_NULL_SPAN = _NullSpan()


def annotation(name: str, **meta):
    """Host span ``name`` with fields ``meta``: a
    ``jax.profiler.TraceAnnotation`` when telemetry is enabled, else a
    no-op.  Either one is a context manager whose ``set_metadata(**meta)``
    adds fields after entry (they are written when the span ends)."""
    if not metrics.enabled():
        return _NULL_SPAN
    return jax.profiler.TraceAnnotation(name, **meta)


@contextlib.contextmanager
def trace(path):
    """Capture a profiled region into ``path`` (TensorBoard/XPlane trace
    directory) — wrap one engine iteration to attribute its kernels:

        with obs.trace("/tmp/ppm-trace"):
            engine.run(state, frontier, max_iters=1, until_empty=False)

    Runs regardless of ``REPRO_OBS`` — an explicit capture request.
    """
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
