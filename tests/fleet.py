"""A one-zone fleet of one to three replicas, every request span-traced.

The engine-level tests of replica and balancer behaviour drive
``VectorizedServingEngine`` through this helper and read what happened
to each request from its span record (segments, replica ordinal,
outcome, attempts, e2e).
"""

import numpy as np

from repro.cluster.traces import SpotTrace
from repro.configs import get_config
from repro.core.autoscaler import ConstantTarget
from repro.core.policy import make_policy
from repro.obs import ObsRecorder
from repro.serving.engine import VectorizedServingEngine

CFG = get_config("llama3.2-1b")
ZONE = "us-west-2a"
STEPS = 70                      # trace minutes; runs last STEPS * 60 s


def run_fleet(requests, *, n_replicas=1, spot_cap=None, **engine_kw):
    """Run ``requests`` on ``n_replicas`` replicas in ``ZONE``.

    With ``spot_cap`` (per-minute spot capacity, length ``STEPS``) the
    replicas are spot instances; without it they are on-demand and
    never preempted.  Returns ``(result, spans)``, ``spans`` indexed by
    request ordinal (arrival order).
    """
    cap = np.zeros((STEPS, 1), np.int32)
    policy = "ondemand_only"
    if spot_cap is not None:
        cap[:, 0] = spot_cap
        policy = "spot_only"
    trace = SpotTrace(zones=(ZONE,), cap=cap, dt=60.0, name="fleet")
    engine_kw.setdefault("timeout_s", 1000.0)
    eng = VectorizedServingEngine(
        trace, make_policy(policy), requests, CFG, itype="g5.48xlarge",
        autoscaler=ConstantTarget(n_replicas),
        obs=ObsRecorder(detail="full", trace_sample=1.0),
        **engine_kw,
    )
    res = eng.run(STEPS * 60.0)
    spans = {r["ordinal"]: r for r in res.obs.span_records()}
    return res, [spans[o] for o in range(len(spans))]


def ready_times(res):
    """Simulated instants at which replicas turned ready, in order."""
    return [
        r["t"] for r in res.obs.records()
        if r["event"] == "lifecycle" and r["phase"] == "ready"
    ]


def segments(span, name):
    return [s for s in span["segments"] if s["name"] == name]
