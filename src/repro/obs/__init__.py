"""repro.obs: unified event tracing, metrics and decision attribution.

The observability substrate every engine shares:

* :mod:`repro.obs.events` — typed, schema-versioned event dataclasses
  for control-plane decisions (with machine-readable *reasons*), replica
  lifecycle transitions, migration plans, preemption warnings and
  windowed data-plane samples.
* :mod:`repro.obs.registry` — a run-scoped metrics registry
  (counters / gauges / histograms with labels) replacing the old
  process-global ``FALLBACK_COUNTS`` module dicts.
* :mod:`repro.obs.recorder` — the per-run :class:`ObsRecorder` that the
  cluster simulator and serving engines emit into, with a ``detail``
  level knob (``off`` | ``decisions`` | ``full``).
* :mod:`repro.obs.export` — byte-deterministic JSONL event logs and a
  Chrome-trace-event (Perfetto-loadable) per-replica timeline.
* :mod:`repro.obs.attribution` — charges each dollar and each failed
  request back to the policy decision (or preemption) that produced it.
* :mod:`repro.obs.hostspan` — ``host_span``, the scenario engine's host
  work marked on ``jax.profiler``'s timeline (wall time, not simulated
  time).
* ``python -m repro.obs`` — summarize a run, diff two runs, render the
  attribution report, convert a log to a Perfetto trace.

Events are emitted at the *shared* choke points (``ClusterSimulator``,
``MigrationRuntime``, the engine tick), so the vector engine's JSONL is
deterministic (its bytes are pinned in tests/data/engine_record.json)
and the JAX engine reproduces the control-plane stream through its
phase-A replay (tests/test_obs.py).
"""

from repro.obs.attribution import attribution_report
from repro.obs.events import (
    SCHEMA_VERSION,
    AutoscalerTargetEvent,
    Event,
    LaunchFailureEvent,
    MigrationPlanEvent,
    PolicyDecisionEvent,
    PreemptionWarningEvent,
    ReplicaLifecycleEvent,
    SLOBurnEvent,
    WindowSampleEvent,
    control_plane_records,
)
from repro.obs.export import (
    chrome_trace,
    diff_summaries,
    dumps_jsonl,
    read_jsonl,
    summarize,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs.hostspan import host_span
from repro.obs.recorder import DETAIL_LEVELS, ObsRecorder
from repro.obs.registry import (
    MetricsRegistry,
    get_registry,
    use_registry,
)
from repro.obs.slo import (
    SLOBurnConfig,
    SLOBurnMonitor,
    burn_summary,
    burn_table,
)
from repro.obs.spans import SpanCollector, span_sampled

__all__ = [
    "SCHEMA_VERSION",
    "DETAIL_LEVELS",
    "Event",
    "PolicyDecisionEvent",
    "ReplicaLifecycleEvent",
    "MigrationPlanEvent",
    "PreemptionWarningEvent",
    "LaunchFailureEvent",
    "WindowSampleEvent",
    "SLOBurnEvent",
    "AutoscalerTargetEvent",
    "control_plane_records",
    "ObsRecorder",
    "SLOBurnConfig",
    "SLOBurnMonitor",
    "burn_summary",
    "burn_table",
    "SpanCollector",
    "span_sampled",
    "host_span",
    "MetricsRegistry",
    "get_registry",
    "use_registry",
    "dumps_jsonl",
    "write_jsonl",
    "read_jsonl",
    "chrome_trace",
    "write_chrome_trace",
    "summarize",
    "diff_summaries",
    "attribution_report",
]
