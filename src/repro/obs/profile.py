"""`jax.profiler` bridge: device-level traces lined up with our spans.

:func:`profile` wraps a block in a ``jax.profiler`` capture into
``outdir`` (the ``--profile DIR`` flag on the launchers), with the python
tracer off, capturing XLA/TPU activity viewable in TensorBoard or
Perfetto.  While a capture is active (:data:`_active`), every
:func:`repro.obs.trace.span` also opens a ``jax.profiler.TraceAnnotation``
of the same name through :func:`annotation`, so the session's wave spans
(``wave.pack``, ``wave.dispatch``, ``wave.compile``, ``wave.wait``,
``wave.gather``, ``wave.traceback``) sit on the device trace's clock with
their ``ticket``/``rows`` args as event stats.  A harness that starts
``jax.profiler`` itself sets :data:`_active` over its window to get the
same annotations.

When no profile is active, :func:`annotation` returns a shared no-op
context manager (no allocation), mirroring the disabled-mode contract of
the tracer.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

__all__ = ["active", "annotation", "profile"]

# Set only while a profile is running; annotation() and obs.trace.span()
# gate on it so steady-state code pays one branch when not profiling.
_active = False

_NULL_CTX = contextlib.nullcontext()


def active() -> bool:
    return _active


@contextlib.contextmanager
def profile(outdir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace of the block into ``outdir``
    (``None`` → no-op, so callers can pass an optional CLI flag straight
    through).  The python tracer is off, so the trace holds the device's
    ops and the program's spans, as the benchmark's traced runs do.  View
    with TensorBoard's profile plugin or Perfetto."""
    global _active
    if not outdir:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    _active = True
    try:
        with jax.profiler.trace(outdir, profiler_options=opts):
            yield
    finally:
        _active = False


def annotation(name: str, **args):
    """A named ``TraceAnnotation`` scope (``args`` become its event
    stats) when a profile is active, else a shared no-op context
    manager."""
    if not _active:
        return _NULL_CTX
    import jax

    return jax.profiler.TraceAnnotation(name, **args)
