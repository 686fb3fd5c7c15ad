"""Pinned answers of the retired per-request object engine.

``tests/data/engine_record.json`` holds what the per-request object
engine (``ServingSimulator``) answered on each scenario that the
engine-equivalence tests used to run it against the vector engine.  The
vector engine matched it bit for bit when it was recorded; these
helpers hold today's engines to the same numbers at the tolerances
those tests used.

Per scenario the record keeps the run's counts, cost and availability,
the latency sample (and, in token mode, the TTFT sample and the KV
accounting) as count, sum, min, max and percentiles 1..99, and for the
event and span runs the SHA-256 of the serialized JSONL.
"""

import functools
import hashlib
import json
import os

import numpy as np
import pytest

_PATH = os.path.join(os.path.dirname(__file__), "data", "engine_record.json")

_COUNTS = ("n_requests", "n_completed", "n_failed", "n_preemptions",
           "n_launch_failures", "n_retried_requests", "lost_kv_tokens")
_TOKEN_COUNTS = ("n_slo_ok", "n_kv_preempted_seqs", "n_killed_queued",
                 "lost_prefill_tokens", "lost_decode_tokens",
                 "n_drained_seqs", "n_migrated_seqs", "migrated_kv_tokens",
                 "saved_prefill_tokens", "saved_decode_tokens")

@functools.lru_cache(maxsize=None)
def _scenarios():
    with open(_PATH, encoding="utf-8") as f:
        return json.load(f)["scenarios"]


def scenario(name):
    """The recorded answers of one scenario (read-only)."""
    return _scenarios()[name]


def sha256(text):
    return hashlib.sha256(text.encode("utf-8")).hexdigest()


def _assert_sample(sample, want, atol, what):
    x = np.sort(np.asarray(sample, dtype=np.float64))
    assert x.size == want["count"], what
    if not x.size:
        return
    got = [x[0], x[-1], x.sum()]
    ref = [want["min"], want["max"], want["sum"]]
    np.testing.assert_allclose(got, ref, atol=atol, rtol=0, err_msg=what)
    np.testing.assert_allclose(
        np.percentile(x, np.arange(1, 100)), want["pct_1_99"],
        atol=atol, rtol=0, err_msg=what,
    )


def assert_matches(res, name, *, atol=1e-6):
    """``res`` (a ServingResult) gives the recorded answers of ``name``."""
    want = scenario(name)
    for k in _COUNTS:
        assert getattr(res, k) == want[k], k
    assert res.total_cost == pytest.approx(want["total_cost"], rel=1e-9)
    assert res.availability == pytest.approx(want["availability"],
                                             abs=1e-12)
    _assert_sample(res.latencies_s, want["latency_s"], atol, "latency")
    if "token" not in want:
        assert res.token is None
        return
    tok, wtok = res.token, want["token"]
    for k in _TOKEN_COUNTS:
        assert getattr(tok, k) == wtok[k], k
    assert tok.migration_transfer_s == pytest.approx(
        wtok["migration_transfer_s"]
    )
    _assert_sample(tok.ttft_s, wtok["ttft_s"], atol, "ttft")
