"""Token-level continuous-batching replica model (``repro.serving.token``).

The request-level replica model prices a request with one frozen
``service_s`` number and an ad-hoc interference factor.  This package
models what an LLM replica actually does: iteration-level (Orca-style)
batching where requests join/leave per decode step, a KV-cache token
budget derived from the same HBM arithmetic as
``LatencyModel.max_concurrency``, a batch-size-dependent decode step from
the HBM roofline (weight reads amortized across the batch, KV reads per
sequence), chunked prefill, and preemptions that destroy in-flight KV
state so retried requests re-prefill elsewhere.

Select it per run with ``sim.replica_model: token`` in a ``ServiceSpec``;
tune it with the ``serving:`` section.  ``VectorizedServingEngine``
drives one :class:`ContinuousBatch` per replica slot through a batched
step loop (the JAX engine hands token cells to it).  Runs in token mode
attach a :class:`TokenStats` (TTFT/TPOT percentiles, windowed
goodput-vs-SLO, preemption KV-loss accounting) to their
``ServingResult``.
"""

from repro.serving.token.batch import (
    ContinuousBatch,
    KillReport,
    TokenCompletion,
)
from repro.serving.token.config import (
    TokenEngineConfig,
    TokenSchedulerConfig,
    UNBOUNDED_KV_TOKENS,
)
from repro.serving.token.metrics import TokenRecord, TokenStats

__all__ = [
    "ContinuousBatch",
    "KillReport",
    "TokenCompletion",
    "TokenEngineConfig",
    "TokenSchedulerConfig",
    "TokenRecord",
    "TokenStats",
    "UNBOUNDED_KV_TOKENS",
]
