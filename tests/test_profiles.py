"""Kernel-profile subsystem: compat shim, step-time tables, profiled latency.

Covers the three layers the profile data plane spans:

* ``kernels/compat.py`` resolves the pinned JAX's Pallas TPU names and
  fails loudly without them — exercised via stand-in modules;
* ``profiles/`` schema round-trips, version gating, directory merging,
  and a real (tiny) profiler run through the interpret-mode kernels;
* ``ProfiledLatencyModel`` reproduces the measured step times from a
  profile JSON (the round-trip the serving layer depends on), and the
  spec/builder wiring falls back to the roofline when no entry matches.
"""

import dataclasses
import json
import math
import types

import pytest

from repro.cluster.catalog import (
    ACCEL_HBM_BYTES_PER_S,
    InstanceType,
    default_catalog,
    hbm_bandwidth,
)
from repro.configs import get_config
from repro.kernels import compat
from repro.profiles import (
    ProfileEntry,
    ProfileSchemaError,
    ProfileTable,
    load_profiles,
    profile_model,
)
from repro.serving.latency import (
    LatencyModel,
    ProfiledLatencyModel,
    make_latency_model,
)

CAT = default_catalog()


# ---------------------------------------------------------------------------
# compat shim
# ---------------------------------------------------------------------------


class _Params:
    def __init__(self, dimension_semantics=None, **kw):
        self.dimension_semantics = dimension_semantics
        self.kw = kw


def test_compat_resolves_new_spelling():
    mod = types.SimpleNamespace(CompilerParams=_Params)
    assert compat.resolve_compiler_params_cls(mod) is _Params


def test_compat_raises_outside_supported_range():
    with pytest.raises(ImportError, match="pyproject"):
        compat.resolve_compiler_params_cls(types.SimpleNamespace())
    with pytest.raises(ImportError):
        compat.resolve_vmem(types.SimpleNamespace())


def test_compat_installed_jax_resolves():
    """The installed JAX has the pinned names, and the shim uses them."""
    p = compat.compiler_params(
        dimension_semantics=("parallel", "arbitrary")
    )
    assert tuple(p.dimension_semantics) == ("parallel", "arbitrary")
    import jax.experimental.pallas.tpu as pltpu

    assert compat.CompilerParams is pltpu.CompilerParams
    assert compat.VMEM is pltpu.VMEM


# ---------------------------------------------------------------------------
# catalog HBM bandwidth table
# ---------------------------------------------------------------------------


def test_catalog_itypes_have_bandwidth():
    for t in CAT.instance_types:
        assert t.hbm_bytes_per_s == ACCEL_HBM_BYTES_PER_S[t.accelerator]


def test_unknown_accelerator_raises():
    with pytest.raises(KeyError, match="HBM bandwidth"):
        hbm_bandwidth("H9000")
    with pytest.raises(KeyError, match="H9000"):
        InstanceType("x1", "aws", "H9000", 1, 1.0, 0.3)


def test_unknown_accelerator_with_explicit_bandwidth_ok():
    t = InstanceType("x1", "aws", "H9000", 2, 1.0, 0.3,
                     hbm_bytes_per_s=1.5e12)
    assert t.hbm_bytes_per_s == 1.5e12
    lm = LatencyModel.for_model(get_config("llama3.2-1b"), t)
    assert lm.hbm_bytes_per_s == 2 * 1.5e12 * lm.mbu_decode


def test_latency_bandwidth_comes_from_catalog():
    """No silent 0.8 TB/s default: model uses the instance's table value."""
    t = CAT.instance_type("g5.48xlarge")     # A10G: 0.6 TB/s
    lm = LatencyModel.for_model(get_config("llama3.2-1b"), t)
    assert lm.hbm_bytes_per_s == pytest.approx(
        t.accel_count * 0.6e12 * lm.mbu_decode
    )


# ---------------------------------------------------------------------------
# profile schema
# ---------------------------------------------------------------------------


def _entry(model="llama3.2-1b", accel="A10G", mfu=0.31, mbu=0.55):
    return ProfileEntry(
        model=model, accelerator=accel, backend="tpu", mode="compiled",
        prefill_tokens=256, prefill_flops=1e12, prefill_wall_s=0.01,
        decode_cache_tokens=512, decode_steps=4,
        decode_bytes=1e9, decode_wall_s=0.001,
        mfu_prefill=mfu, mbu_decode=mbu,
    )


def test_profile_table_json_round_trip(tmp_path):
    table = ProfileTable(jax_version="0.0.0", backend="tpu",
                         mode="compiled")
    table.add(_entry())
    path = str(tmp_path / "t.json")
    table.save(path)
    back = ProfileTable.load(path)
    assert back.lookup("llama3.2-1b", "A10G") == _entry()
    assert back.lookup("llama3.2-1b", "V100") is None


def test_profile_schema_version_gate(tmp_path):
    path = tmp_path / "bad.json"
    d = ProfileTable().to_dict()
    d["schema_version"] = 99
    path.write_text(json.dumps(d))
    with pytest.raises(ProfileSchemaError, match="schema_version"):
        ProfileTable.load(str(path))


def test_profile_entry_key_mismatch_rejected(tmp_path):
    path = tmp_path / "bad.json"
    d = ProfileTable().to_dict()
    d["entries"] = {"wrong|key": _entry().to_dict()}
    path.write_text(json.dumps(d))
    with pytest.raises(ProfileSchemaError, match="keyed"):
        ProfileTable.load(str(path))


def test_load_profiles_directory_merge(tmp_path):
    a = ProfileTable()
    a.add(_entry(accel="A10G", mfu=0.1))
    a.save(str(tmp_path / "a.json"))
    b = ProfileTable()
    b.add(_entry(accel="A10G", mfu=0.9))   # later file wins
    b.add(_entry(accel="V100"))
    b.save(str(tmp_path / "b.json"))
    merged = load_profiles(str(tmp_path))
    assert len(merged.entries) == 2
    assert merged.lookup("llama3.2-1b", "A10G").mfu_prefill == 0.9


def test_load_profiles_missing_ok(tmp_path):
    assert load_profiles(str(tmp_path / "nope"), missing_ok=True).entries \
        == {}
    with pytest.raises(ProfileSchemaError):
        load_profiles(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# profiler (tiny real run through the interpret kernels)
# ---------------------------------------------------------------------------


def test_profiler_measures_llama_interpret():
    itype = CAT.instance_type("v5e-8")
    e = profile_model(
        "llama3.2-1b", itype,
        prefill_tokens=64, cache_tokens=128, repeats=1,
    )
    assert e.mode == "interpret" and e.accelerator == "TPUv5e"
    assert e.prefill_wall_s > 0 and e.decode_wall_s > 0
    assert 0 < e.mfu_prefill < 1 and 0 < e.mbu_decode < 1
    assert math.isclose(
        e.prefill_flops_per_s * (itype.accel_count
                                 * itype.peak_bf16_tflops * 1e12) ** -1,
        e.mfu_prefill,
    )


def test_run_cli_refuses_to_clobber_unreadable_table(tmp_path, capsys):
    from repro.profiles import run as profiles_run

    out = tmp_path / "t.json"
    out.write_text("{not json")
    rc = profiles_run.main([
        "--models", "llama3.2-1b", "--itype", "v5e-8",
        "--prefill-tokens", "64", "--cache-tokens", "128",
        "--repeats", "1", "--out", str(out),
    ])
    assert rc == 1
    assert "cannot be merged" in capsys.readouterr().err
    assert out.read_text() == "{not json"   # untouched


# ---------------------------------------------------------------------------
# ProfiledLatencyModel round trip
# ---------------------------------------------------------------------------


def test_profiled_latency_reproduces_measured_step_times(tmp_path):
    """profile JSON -> service_s consistent with the table's throughputs:
    prefill_s = 2·N_active·P / measured_flops_per_s and decode seconds/token
    = weight_bytes / measured_bytes_per_s (the roofline with measured
    MFU/MBU collapses to exactly the measured throughput)."""
    cfg = get_config("llama3.2-1b")
    itype = CAT.instance_type("g5.48xlarge")
    entry = _entry(accel=itype.accelerator)
    table = ProfileTable(jax_version="0", backend="tpu", mode="compiled")
    table.add(entry)
    path = str(tmp_path / "p.json")
    table.save(path)

    lm = make_latency_model(
        cfg, itype, model_id="llama3.2-1b", source="profile", profile=path
    )
    assert isinstance(lm, ProfiledLatencyModel)
    assert lm.profile_mode == "compiled"

    peak_flops = itype.accel_count * itype.peak_bf16_tflops * 1e12
    peak_bytes = itype.accel_count * itype.hbm_bytes_per_s
    P = 200
    want_prefill = 2.0 * lm._active_params * P / (
        peak_flops * entry.mfu_prefill
    )
    assert lm.prefill_s(P) == pytest.approx(want_prefill, rel=1e-12)
    want_decode = 2.0 * lm._active_params / (peak_bytes * entry.mbu_decode)
    assert lm.decode_s_per_token() == pytest.approx(want_decode, rel=1e-12)
    assert lm.service_s(P, 10) == pytest.approx(
        lm.overhead_s + want_prefill + 10 * want_decode, rel=1e-12
    )


def test_make_latency_model_roofline_matches_legacy():
    cfg = get_config("llama3.2-1b")
    itype = CAT.instance_type("g5.48xlarge")
    a = make_latency_model(cfg, itype, model_id="llama3.2-1b")
    b = LatencyModel.for_model(cfg, itype)
    assert a.service_s(100, 50) == b.service_s(100, 50)
    assert not isinstance(a, ProfiledLatencyModel)


def test_make_latency_model_profile_fallback_warns(tmp_path):
    cfg = get_config("llama3.2-1b")
    itype = CAT.instance_type("g5.48xlarge")
    with pytest.warns(UserWarning, match="falling back"):
        lm = make_latency_model(
            cfg, itype, model_id="llama3.2-1b", source="profile",
            profile=str(tmp_path / "absent"),
        )
    assert type(lm) is LatencyModel


def test_make_latency_model_rejects_unknown_source():
    cfg = get_config("llama3.2-1b")
    itype = CAT.instance_type("g5.48xlarge")
    with pytest.raises(ValueError, match="latency source"):
        make_latency_model(cfg, itype, model_id="llama3.2-1b",
                           source="vibes")


# ---------------------------------------------------------------------------
# spec wiring
# ---------------------------------------------------------------------------


def test_latency_spec_round_trip_and_validation():
    from repro.service import LatencySpec, SpecError, spec_from_dict

    spec = spec_from_dict({
        "name": "x", "model": "llama3.2-1b", "trace": "aws-1",
        "latency": {"source": "profile", "profile": "some/dir"},
    })
    assert spec.latency == LatencySpec(source="profile",
                                       profile="some/dir")
    assert spec_from_dict(spec.to_dict()) == spec
    with pytest.raises(SpecError, match="latency.source"):
        spec_from_dict({
            "name": "x", "model": "llama3.2-1b", "trace": "aws-1",
            "latency": {"source": "vibes"},
        })
    with pytest.raises(SpecError, match="unknown keys"):
        spec_from_dict({
            "name": "x", "model": "llama3.2-1b", "trace": "aws-1",
            "latency": {"src": "roofline"},
        })


def test_builder_injects_profiled_model(tmp_path):
    from repro.service import spec_from_dict
    from repro.service.builder import build_service

    itype = CAT.instance_type("g5.48xlarge")
    table = ProfileTable(jax_version="0", backend="tpu", mode="compiled")
    table.add(_entry(accel=itype.accelerator))
    path = str(tmp_path / "p.json")
    table.save(path)

    base = {
        "name": "x", "model": "llama3.2-1b", "trace": "aws-1",
        "resources": {"instance_type": "g5.48xlarge"},
        "workload": {"kind": "poisson", "rate_per_s": 0.5},
        "sim": {"duration_hours": 1.0},
    }
    for engine in ("vector", "jax"):
        spec = spec_from_dict({
            **base,
            "latency": {"source": "profile", "profile": path},
            "sim": {"duration_hours": 1.0, "engine": engine},
        })
        sim = build_service(spec).simulator
        assert isinstance(sim.latency_model, ProfiledLatencyModel), engine
        assert sim.latency_model.mfu_prefill == 0.31


def test_profiled_model_dataclass_provenance():
    cfg = get_config("llama3.2-1b")
    itype = CAT.instance_type("g5.48xlarge")
    lm = ProfiledLatencyModel.from_entry(
        cfg, itype, _entry(accel=itype.accelerator), path="p.json"
    )
    d = dataclasses.asdict(lm)
    assert d["profile_path"] == "p.json"
    assert d["profile_backend"] == "tpu"
    assert d["mfu_prefill"] == 0.31 and d["mbu_decode"] == 0.55
