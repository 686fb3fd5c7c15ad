"""Serving data plane: latency model, token batches, serving engines.

Two engines share the control plane (policy / autoscaler / controller)
and the latency model:

* ``engine.py`` — :class:`VectorizedServingEngine`, the reference engine:
  NumPy array state, event-skipping sub-ticks, both replica models;
* ``jaxengine/`` — :class:`~repro.serving.jaxengine.JaxServingEngine`,
  the fast engine: the request-model data plane as one ``lax.scan``,
  whole scenario matrices as one ``vmap``-ed program.

``result.py`` holds what both return (:class:`ServingResult`).

Live token-serving (real JAX prefill/decode) lives in
``examples/serve_llm.py`` / ``benchmarks/engine_bench.py``.
"""

from repro.serving.engine import LB_KINDS, VectorizedServingEngine
from repro.serving.latency import (
    LatencyModel,
    ProfiledLatencyModel,
    make_latency_model,
)
from repro.serving.result import REPLICA_MODELS, ServingResult
from repro.serving.token import (
    ContinuousBatch,
    TokenEngineConfig,
    TokenSchedulerConfig,
    TokenStats,
)

__all__ = [
    "LB_KINDS",
    "LatencyModel",
    "ProfiledLatencyModel",
    "make_latency_model",
    "REPLICA_MODELS",
    "ServingResult",
    "ContinuousBatch",
    "TokenEngineConfig",
    "TokenSchedulerConfig",
    "TokenStats",
    "VectorizedServingEngine",
]
