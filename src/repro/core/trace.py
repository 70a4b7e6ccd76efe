"""Casper's spans and set-up events: the names and the one way to record them.

Two channels, both JAX's own, so whatever already reads a JAX profile or
listens to ``jax.monitoring`` sees Casper's work without further set-up:

* :func:`span` opens a ``jax.profiler.TraceAnnotation``: a host span on
  the profiler's ``/host:CPU`` plane, on the device trace's clock, so a
  gap in the device's work can be named by the Casper call it fell in;
* ``span(name, event=True)`` also records
  ``jax.monitoring.record_event_duration_secs("/casper/" + name, dt)``
  on exit, for work that happens once per plan (lowering, autotuning,
  verification), where a listener wants a duration without a profile.

The third channel is the kernel itself: every fused ``pallas_call`` is
named :data:`KERNEL_NAME` and carries a metadata dict of strings
(``repro.kernels.engine._kernel_tag``) that the compiled op's HLO text,
and so each of its device events in a profile, holds as
``kernel_metadata={...}``.

Nothing is kept between calls and nothing is switched on or off: with
no profiler running and no listener registered a span costs one
TraceMe object.
"""
from __future__ import annotations

import contextlib
import time

import jax

#: ``CasperEngine.run``: one call, the jitted dispatch included (a
#: profile span only: it is the hot path).
RUN = "casper.run"
#: A plan-cache miss: the plan's construction and its verification.
LOWER = "casper.lower"
#: A tile autotune run by lowering (``tile="auto"``).
AUTOTUNE = "casper.autotune"
#: The static verification of a freshly lowered plan.
VERIFY = "casper.verify"

#: Prefix of the ``jax.monitoring`` duration events ``span`` records.
EVENT_PREFIX = "/casper/"

#: ``name`` of every fused kernel's ``pallas_call``.
KERNEL_NAME = "casper_fused"


@contextlib.contextmanager
def span(name: str, *, event: bool = False):
    """A profiler span named ``name`` around the block; with ``event``
    also a ``/casper/<name>`` duration event when the block exits."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    if event:
        jax.monitoring.record_event_duration_secs(
            EVENT_PREFIX + name, time.perf_counter() - t0)
