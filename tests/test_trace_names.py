"""The names a profile of the accelerator paths carries.

A profile charges device operations to the ``jax.named_scope`` they were
traced under (their ``op_name`` metadata) and host time to
``repro.obs.host_span`` events.  Readings of a profile match these names
by string, so a rename would silently leave a reading empty; these tests
pin every name on the CPU:

* phase B's program is ``jit_lane`` and each stage of its scan step has
  a scope of its own;
* the model step scopes ``embed``, ``layers``, ``logits`` and, in each
  attention block, ``norm``, ``attention`` (with ``kv_write``) and
  ``ffn``; the Pallas kernels run under ``attention``;
* a window/full model (mellum2) scopes each kind of block ``window`` or
  ``full`` under ``layers``, and its serving expert layer ``moe`` under
  ``ffn``, with ``route``, ``sort``, ``experts`` and ``combine`` inside;
* a profiled scenario-engine run holds each engine span, inside the run.
"""

import contextlib
import functools
import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.traces import synth_correlated_trace
from repro.configs import get_config, get_smoke_config
from repro.core.autoscaler import ConstantTarget
from repro.core.policy import make_policy
from repro.experiments import ScenarioSuite
from repro.models import build_model
from repro.obs import hostspan
from repro.service import spec_from_dict
from repro.serving.jaxengine import JaxServingEngine, run_cells
from repro.serving.jaxengine import kernel as K
from repro.workloads import make_workload

STAGES = ("kill", "arrive", "dispatch", "complete", "expire", "start")
BLOCK_SCOPES = ("embed", "layers", "norm", "attention", "kv_write", "ffn",
                "logits")


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _scopes(hlo_text):
    """Every component of every operation's scope path."""
    return {part for name in _op_names(hlo_text) for part in name.split("/")}


# ---------------------------------------------------------------------------
# phase B
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def phase_b_hlo():
    """The compiled phase-B program of a tiny key with kills and expiry."""
    key = K.KernelKey(G=6, W=2, N=8, R=2, Q=4, C=2, NREG=1, E=1, AMAX=2,
                      ATYP=1, lb_rr=False, expire_on=True)
    L = 2
    with jax.enable_x64(True):
        args = (
            np.full((L, 8), np.inf), np.ones((L, 8)),
            np.zeros((L, 8), np.int64), np.zeros((L, 2, 1)),
            np.zeros((L, 2, 2), bool), np.zeros((L, 1), np.int64),
            np.full((L, 1), 6, np.int64), np.zeros(L),
            np.arange(6.0), np.arange(6), np.zeros(6, np.int64),
        )
        return K.get_kernel(key).lower(*args).compile().as_text()


def test_phase_b_module_is_jit_lane(phase_b_hlo):
    assert phase_b_hlo.startswith("HloModule jit_lane,")


@pytest.mark.parametrize("stage", STAGES)
def test_phase_b_stage_has_its_scope(phase_b_hlo, stage):
    scoped = [n for n in _op_names(phase_b_hlo)
              if stage in n.split("/")]
    assert scoped, f"no operation of phase B under scope {stage!r}"


# ---------------------------------------------------------------------------
# model step
# ---------------------------------------------------------------------------


def _step_hlo(impl):
    """Compiled prefill and decode of the smoke command-r (parallel
    block, the served model's layout)."""
    cfg = get_smoke_config("command-r-35b")
    model = build_model(cfg, impl=impl)
    params = model.abstract(jnp.bfloat16)
    B, S = 2, 16
    cache = model.abstract_cache(B, S + 1)
    tok = lambda n: jax.ShapeDtypeStruct((B, n), jnp.int32)  # noqa: E731
    return {
        "prefill": jax.jit(model.prefill).lower(
            params, tok(S), cache).compile().as_text(),
        "decode": jax.jit(model.decode_step).lower(
            params, tok(1), cache).compile().as_text(),
    }


@pytest.fixture(scope="module")
def step_hlo():
    return _step_hlo("blockwise")


@pytest.mark.parametrize("mode", ["prefill", "decode"])
@pytest.mark.parametrize("scope", BLOCK_SCOPES)
def test_model_step_has_scope(step_hlo, mode, scope):
    assert scope in _scopes(step_hlo[mode])


def test_pallas_kernels_run_under_attention(monkeypatch):
    """impl="pallas", the kernels interpreted on the CPU as the smoke
    tests run them: their operations lie under the block's ``attention``
    scope in ``flash_attention`` (prefill) and ``flash_decode``
    (decode).  The custom calls' own names on the chip are pinned by
    ``test_tpu_compile.py``."""
    from repro.kernels import ops

    for name in ("flash_attention", "flash_decode"):
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(ops, name), interpret=True))
    hlo = _step_hlo("pallas")
    for mode, kernel in (("prefill", "flash_attention"),
                         ("decode", "flash_decode")):
        under = [n for n in _op_names(hlo[mode])
                 if "layers/" in n and f"attention/jit({kernel})/" in n]
        assert under, f"{mode}: no {kernel} operation under attention"


MELLUM_SCOPES = {
    # scope: the scope it lies directly under
    "window": "layers", "full": "layers",
    "moe": "ffn", "route": "moe", "sort": "moe", "experts": "moe",
    "combine": "moe",
}


@pytest.fixture(scope="module")
def mellum_hlo():
    """Compiled prefill (past the window) and decode of the smoke
    mellum2."""
    model = build_model(get_smoke_config("mellum2-12b"))
    params = model.abstract(jnp.bfloat16)
    B, S = 2, 24
    cache = model.abstract_cache(B, S + 1)
    tok = lambda n: jax.ShapeDtypeStruct((B, n), jnp.int32)  # noqa: E731
    return {
        "prefill": jax.jit(model.prefill).lower(
            params, tok(S), cache).compile().as_text(),
        "decode": jax.jit(model.decode_step).lower(
            params, tok(1), cache).compile().as_text(),
    }


@pytest.mark.parametrize("mode", ["prefill", "decode"])
@pytest.mark.parametrize("scope", sorted(MELLUM_SCOPES))
def test_mellum_step_has_scope(mellum_hlo, mode, scope):
    """Each scope holds operations under the scope it belongs to
    (``.../ffn/moe/route/...``), inside the layer scan."""
    outer = MELLUM_SCOPES[scope]

    def placed(parts):
        i = parts.index(scope)
        return "layers" in parts[:i] and outer in parts[:i]

    names = [n.split("/") for n in _op_names(mellum_hlo[mode])]
    assert any(scope in p and placed(p) for p in names), scope


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


def test_span_names_are_distinct_and_prefixed():
    assert len(set(hostspan.ENGINE_SPANS)) == len(hostspan.ENGINE_SPANS)
    assert all(n.startswith("repro.engine.") for n in hostspan.ENGINE_SPANS)


def test_host_span_needs_no_jax(monkeypatch):
    """Without JAX imported the span is a null context (NumPy engines
    never import JAX through it); with JAX it is a profiler annotation."""
    assert isinstance(hostspan.host_span(hostspan.BUILD),
                      jax.profiler.TraceAnnotation)
    monkeypatch.delitem(sys.modules, "jax")
    with hostspan.host_span(hostspan.BUILD) as span:
        assert span is None


def test_profiled_engine_run_holds_every_span(tmp_path):
    """A 2-cell, quarter-hour matrix on the JAX engine, profiled warm:
    one phase-A span per cell, one build per cell, and every other span
    of the run's path, each inside the run."""
    from jax.profiler import ProfileData

    suite = ScenarioSuite.from_spec(spec_from_dict({
        "name": "trace-names",
        "model": "command-r-35b",
        "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "replica_policy": {"name": "spothedge"},
        "autoscaler": {"kind": "constant", "target": 3},
        "workload": {"kind": "poisson", "rate_per_s": 0.5, "seed": 17},
        "sim": {"duration_hours": 0.25, "timeout_s": 60.0,
                "concurrency": 2, "drain_s": 300.0},
        "sweep": {"policies": ["spothedge"], "seeds": [0, 1]},
    }))
    suite.run(engine="jax")          # compiles outside the profile
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("run"):
            report = suite.run(engine="jax")
    assert len(report.cells) == 2
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name == "run" or e.name.startswith("repro.")]
    ((_, r0, r1),) = [e for e in events if e[0] == "run"]
    count = {n: 0 for n in hostspan.ENGINE_SPANS}
    for name, s, e in events:
        if name != "run":
            count[name] += 1
            assert r0 <= s <= e <= r1, name
    assert count[hostspan.PHASE_A] == count[hostspan.BUILD] == 2
    assert count[hostspan.FALLBACK] == 0
    for name in (hostspan.PACK, hostspan.TO_DEVICE, hostspan.EXECUTE,
                 hostspan.FROM_DEVICE, hostspan.ASSEMBLE):
        assert count[name] >= 1, name


def test_numpy_rerun_is_a_fallback_span(monkeypatch):
    """A token-model cell runs on the NumPy engine inside one fallback
    span, and phase A and phase B never start."""
    seen = []

    @contextlib.contextmanager
    def record(name):
        seen.append(name)
        yield

    monkeypatch.setattr(hostspan, "host_span", record)
    zones = ["us-west-2a", "us-west-2b"]
    trace = synth_correlated_trace(zones, {z: z[:-1] for z in zones},
                                   steps=60, dt=60.0, seed=3,
                                   max_capacity=4, name="mini")
    reqs = make_workload("poisson", rate_per_s=0.5, seed=3).generate(1800.0)
    eng = JaxServingEngine(
        trace, make_policy("spothedge"), reqs,
        get_config("command-r-35b"), itype="g5.48xlarge",
        autoscaler=ConstantTarget(2), timeout_s=60.0, concurrency=2,
        replica_model="token",
    )
    (res,) = run_cells([eng], [1800.0])
    assert res.n_requests > 0
    assert seen == [hostspan.FALLBACK]
