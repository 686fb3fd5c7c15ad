"""Host spans on the profiler's clock.

``host_span(name)`` marks a stretch of host work for ``jax.profiler``:
while a profiler session runs, each span becomes a host event in its
trace, on the same clock as the device's operations, so the time the
device waits on the host can be charged to the host work that held it.
With no session the span costs well under a microsecond.  It keeps
nothing itself: the profiler is the store.

A process that has not imported JAX gets a null context, so the NumPy
engines never pull JAX in through a span.

The scenario engine's spans, by where the work happens:

* ``BUILD``: one cell's service build (``ScenarioSuite``);
* ``PHASE_A``: one cell's control-plane replay (``record_schedule``);
* ``PACK``: stacking a shape group's lanes and sizing its ``KernelKey``;
* ``TO_DEVICE``, ``EXECUTE``, ``FROM_DEVICE``: phase B's inputs to the
  device, the program until its outputs are ready, the outputs back;
* ``ASSEMBLE``: lane outputs to results, spans and report cells;
* ``FALLBACK``: one cell's rerun on the NumPy engine.
"""

from __future__ import annotations

import contextlib
import sys

BUILD = "repro.engine.build"
PHASE_A = "repro.engine.phase_a"
PACK = "repro.engine.pack"
TO_DEVICE = "repro.engine.to_device"
EXECUTE = "repro.engine.execute"
FROM_DEVICE = "repro.engine.from_device"
ASSEMBLE = "repro.engine.assemble"
FALLBACK = "repro.engine.fallback"

ENGINE_SPANS = (BUILD, PHASE_A, PACK, TO_DEVICE, EXECUTE, FROM_DEVICE,
                ASSEMBLE, FALLBACK)


def host_span(name: str):
    """A context that marks ``name`` on the profiler's host timeline."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)
