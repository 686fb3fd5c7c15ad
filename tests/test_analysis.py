"""Tests for repro.analysis — the repo-aware static invariant checker.

Each pass gets a known-bad fixture tree that must be flagged and a
known-good twin that must not; a pass that silently stopped firing
fails its bad-fixture test.  The final gate test runs the full checker
against this repository checkout and requires a clean (fully exempted)
report — the same bar CI enforces.
"""

import json
import os
import textwrap

import pytest

from repro.analysis import (
    AnalysisReport,
    ExemptionError,
    RULES,
    load_exemptions,
    rule_ids,
    run_analysis,
)
from repro.analysis.core import RepoContext
from repro.analysis.__main__ import main as analysis_main

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_RULES = (
    "determinism",
    "engine-parity",
    "silent-fallback",
    "spec-drift",
    "tracing-hazard",
)


def _write(root, rel, text):
    path = os.path.join(root, *rel.split("/"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(textwrap.dedent(text))


def _findings(report, rule):
    return [f.finding for f in report.findings if f.finding.rule == rule]


def test_all_five_rules_registered():
    assert set(ALL_RULES) <= set(rule_ids())
    for rid in ALL_RULES:
        assert RULES[rid].description


# -- engine-parity -------------------------------------------------------

def _parity_tree(root, engine_body):
    _write(root, "src/repro/service/spec.py", """\
        import dataclasses

        @dataclasses.dataclass
        class SimSpec:
            timeout_s: float = 100.0
            concurrency: int = 4
    """)
    _write(root, "src/repro/serving/jaxengine/kernel.py", """\
        def run_group(spec):
            return spec.timeout_s + spec.concurrency
    """)
    _write(root, "src/repro/serving/engine.py", engine_body)


def test_engine_parity_flags_one_sided_field(tmp_path):
    root = str(tmp_path)
    # the vector engine never consumes timeout_s, the jax kernel does ->
    # parity violation (jax inherits what vector consumes, not the reverse)
    _parity_tree(root, """\
        class VectorizedServingEngine:
            def run(self, spec):
                return spec.concurrency
    """)
    report = run_analysis(root, rules=["engine-parity"])
    found = _findings(report, "engine-parity")
    assert [f.symbol for f in found] == ["SimSpec.timeout_s"]
    assert found[0].path == "src/repro/service/spec.py"
    assert found[0].line > 0
    assert "consumed by the jax engine but not by vector" in found[0].message


def test_engine_parity_clean_when_both_consume(tmp_path):
    root = str(tmp_path)
    _parity_tree(root, """\
        class VectorizedServingEngine:
            def run(self, spec):
                return spec.timeout_s * spec.concurrency
    """)
    report = run_analysis(root, rules=["engine-parity"])
    assert _findings(report, "engine-parity") == []


def test_engine_parity_silent_when_rule_disabled(tmp_path):
    root = str(tmp_path)
    _parity_tree(root, """\
        class VectorizedServingEngine:
            def run(self, spec):
                return spec.concurrency
    """)
    others = [r for r in ALL_RULES if r != "engine-parity"]
    report = run_analysis(root, rules=others)
    assert _findings(report, "engine-parity") == []
    assert not report.ok or True  # disabled rule must not leak findings


# -- determinism ---------------------------------------------------------

BAD_DETERMINISM = """\
    import time

    def stamp(results, done):
        started = time.time()
        out = [k for k in set(results) - set(done)]
        return started, out
"""

GOOD_DETERMINISM = """\
    import time

    def stamp(results, done, clock):
        started = clock.now()
        elapsed = time.perf_counter()
        out = [k for k in sorted(set(results) - set(done))]
        return started, elapsed, out
"""


def test_determinism_flags_wall_clock_and_set_iteration(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/keys.py", BAD_DETERMINISM)
    report = run_analysis(root, rules=["determinism"])
    symbols = {f.symbol for f in _findings(report, "determinism")}
    assert "time.time" in symbols
    assert "set-iteration" in symbols


def test_determinism_clean_twin(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/keys.py", GOOD_DETERMINISM)
    report = run_analysis(root, rules=["determinism"])
    assert _findings(report, "determinism") == []


def test_determinism_flags_repr_keys_and_unseeded_rng(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/experiments/tape.py", """\
        import json
        import numpy as np

        def tape_key(spec):
            return json.dumps(spec, default=repr)

        def jitter():
            rng = np.random.default_rng()
            return rng.random()

        def label(obj):
            return f"cell-{id(obj)}"
    """)
    report = run_analysis(root, rules=["determinism"])
    symbols = {f.symbol for f in _findings(report, "determinism")}
    assert "json.dumps" in symbols
    assert "default_rng" in symbols
    assert "id" in symbols


# -- tracing-hazard ------------------------------------------------------

def test_tracing_flags_backend_query_in_jit(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/kernels/k.py", """\
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def step(x, n):
            if jax.default_backend() == "cpu":
                return x
            return x * n
    """)
    report = run_analysis(root, rules=["tracing-hazard"])
    found = _findings(report, "tracing-hazard")
    assert any("default_backend" in f.message for f in found)
    assert all(f.symbol == "step" for f in found)


def test_tracing_clean_when_query_hoisted(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/kernels/k.py", """\
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("interpret",))
        def step(x, interpret):
            return x * 2

        def run(x):
            interpret = jax.default_backend() == "cpu"
            return step(x, interpret)
    """)
    report = run_analysis(root, rules=["tracing-hazard"])
    assert _findings(report, "tracing-hazard") == []


def test_tracing_follows_helpers_called_from_traced_bodies(tmp_path):
    root = str(tmp_path)
    # hazard is two calls deep: jit body -> helper -> .item()
    _write(root, "src/repro/serving/jaxengine/fastpath.py", """\
        import jax

        def _peek(x):
            return x.item()

        @jax.jit
        def step(x):
            return _peek(x) + 1
    """)
    report = run_analysis(root, rules=["tracing-hazard"])
    found = _findings(report, "tracing-hazard")
    assert any(f.symbol == "_peek" for f in found)


# -- silent-fallback -----------------------------------------------------

def test_silent_fallback_flags_warn_only_handler(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/loader.py", """\
        import warnings

        def load(path):
            try:
                return open(path).read()
            except OSError:
                warnings.warn(f"could not read {path}; using default")
                return ""
    """)
    report = run_analysis(root, rules=["silent-fallback"])
    found = _findings(report, "silent-fallback")
    assert [f.symbol for f in found] == ["warn-only-fallback"]


def test_silent_fallback_clean_with_counter(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/loader.py", """\
        import collections
        import warnings

        FALLBACK_COUNTS = collections.Counter()

        def load(path):
            try:
                return open(path).read()
            except OSError:
                FALLBACK_COUNTS[path] += 1
                warnings.warn(f"could not read {path}; using default")
                return ""
    """)
    report = run_analysis(root, rules=["silent-fallback"])
    assert _findings(report, "silent-fallback") == []


def test_silent_fallback_flags_swallowed_exception(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/cluster/util.py", """\
        def maybe(x):
            try:
                return x.compute()
            except Exception:
                return None
    """)
    report = run_analysis(root, rules=["silent-fallback"])
    found = _findings(report, "silent-fallback")
    assert [f.symbol for f in found] == ["swallowed-except"]


def test_silent_fallback_flags_announced_fallback_without_counter(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/pick.py", """\
        import warnings

        def pick(entry, default):
            if entry is None:
                warnings.warn("no entry; falling back to the default model")
                return default
            return entry
    """)
    report = run_analysis(root, rules=["silent-fallback"])
    found = _findings(report, "silent-fallback")
    assert [f.symbol for f in found] == ["pick"]


# -- spec-drift ----------------------------------------------------------

def _drift_tree(root, *, loader_mentions, example_mentions):
    _write(root, "src/repro/service/spec.py", """\
        import dataclasses

        @dataclasses.dataclass
        class SimSpec:
            duration_hours: float = 4.0
            shiny_knob: int = 3
    """)
    loader = "def load(d):\n    return d['duration_hours']\n"
    if loader_mentions:
        loader += "\n\ndef load2(d):\n    return d['shiny_knob']\n"
    _write(root, "src/repro/service/loader.py", loader)
    _write(root, "src/repro/service/builder.py",
           "def build(spec):\n    return spec\n")
    example = "sim:\n  duration_hours: 4.0\n"
    if example_mentions:
        example += "  # shiny_knob: 3\n"
    _write(root, "examples/service.yaml", example)


def test_spec_drift_flags_unhandled_and_undemonstrated(tmp_path):
    root = str(tmp_path)
    _drift_tree(root, loader_mentions=False, example_mentions=False)
    report = run_analysis(root, rules=["spec-drift"])
    found = _findings(report, "spec-drift")
    assert {f.symbol for f in found} == {"SimSpec.shiny_knob"}
    messages = " ".join(f.message for f in found)
    assert "loader/builder" in messages and "examples/" in messages


def test_spec_drift_clean_twin_commented_key_counts(tmp_path):
    root = str(tmp_path)
    # a commented '# shiny_knob: 3' line demonstrates the knob
    _drift_tree(root, loader_mentions=True, example_mentions=True)
    report = run_analysis(root, rules=["spec-drift"])
    assert _findings(report, "spec-drift") == []


# -- parse errors --------------------------------------------------------

def test_parse_error_becomes_finding(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/broken.py", "def f(:\n")
    report = run_analysis(root, rules=["determinism"])
    assert [f.finding.rule for f in report.findings] == ["parse-error"]
    assert not report.ok


# -- report schema -------------------------------------------------------

def test_report_round_trip(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/keys.py", BAD_DETERMINISM)
    report = run_analysis(root, rules=["determinism"])
    assert not report.ok
    out = os.path.join(root, "artifacts", "analysis", "report.json")
    report.save(out)
    loaded = AnalysisReport.load(out)
    assert loaded.to_dict() == report.to_dict()
    assert loaded.n_active == report.n_active
    # byte-determinism: saving the loaded report reproduces the file
    out2 = os.path.join(root, "report2.json")
    loaded.save(out2)
    with open(out) as a, open(out2) as b:
        assert a.read() == b.read()


def test_report_schema_gate(tmp_path):
    with pytest.raises(ValueError, match="schema"):
        AnalysisReport.from_dict({"schema": 99, "findings": []})


def test_report_json_shape(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/keys.py", GOOD_DETERMINISM)
    report = run_analysis(root, rules=["determinism"])
    d = report.to_dict()
    assert d["schema"] == 1
    assert d["tool"] == "repro.analysis"
    for key in ("rules", "n_files_scanned", "n_findings", "n_active",
                "n_exempted", "findings_by_rule", "findings",
                "unused_exemptions"):
        assert key in d


# -- exemptions ----------------------------------------------------------

def _exemptions_tree(root, entries):
    _write(root, "src/repro/serving/keys.py", BAD_DETERMINISM)
    doc = {"schema": 1, "exemptions": entries}
    _write(root, "analysis_exemptions.json", json.dumps(doc))


def test_exemption_silences_finding_and_records_justification(tmp_path):
    root = str(tmp_path)
    _exemptions_tree(root, [
        {"rule": "determinism", "path": "src/repro/serving/keys.py",
         "justification": "fixture: keys module is measurement-only"},
    ])
    report = run_analysis(root, rules=["determinism"])
    assert report.ok
    assert report.n_exempted > 0
    assert all(
        f.justification == "fixture: keys module is measurement-only"
        for f in report.findings
    )


def test_exemption_unknown_rule_errors(tmp_path):
    root = str(tmp_path)
    _exemptions_tree(root, [
        {"rule": "no-such-rule", "path": "src/repro/serving/keys.py",
         "justification": "x"},
    ])
    with pytest.raises(ExemptionError, match="unknown rule"):
        run_analysis(root, rules=["determinism"])


def test_exemption_stale_path_errors(tmp_path):
    root = str(tmp_path)
    _exemptions_tree(root, [
        {"rule": "determinism", "path": "src/repro/serving/gone.py",
         "justification": "x"},
    ])
    with pytest.raises(ExemptionError, match="does not exist"):
        run_analysis(root, rules=["determinism"])


def test_exemption_missing_justification_errors(tmp_path):
    root = str(tmp_path)
    _exemptions_tree(root, [
        {"rule": "determinism", "path": "src/repro/serving/keys.py"},
    ])
    with pytest.raises(ExemptionError, match="justification"):
        run_analysis(root, rules=["determinism"])


def test_exemption_unknown_key_errors(tmp_path):
    root = str(tmp_path)
    _exemptions_tree(root, [
        {"rule": "determinism", "path": "src/repro/serving/keys.py",
         "justification": "x", "reviewer": "me"},
    ])
    with pytest.raises(ExemptionError, match="unknown keys"):
        run_analysis(root, rules=["determinism"])


def test_unused_exemption_is_reported_and_fails_cli(tmp_path):
    root = str(tmp_path)
    _write(root, "src/repro/serving/keys.py", GOOD_DETERMINISM)
    doc = {"schema": 1, "exemptions": [
        {"rule": "determinism", "path": "src/repro/serving/keys.py",
         "justification": "stale: nothing to exempt any more"},
    ]}
    _write(root, "analysis_exemptions.json", json.dumps(doc))
    report = run_analysis(root, rules=["determinism"])
    assert report.ok  # no active findings ...
    assert len(report.unused_exemptions) == 1  # ... but a stale entry
    rc = analysis_main(["--root", root, "--rules", "determinism",
                        "--out", "-"])
    assert rc == 1


# -- CLI -----------------------------------------------------------------

def test_cli_exit_codes_and_report_artifact(tmp_path, capsys):
    root = str(tmp_path)
    _write(root, "src/repro/serving/keys.py", BAD_DETERMINISM)
    rc = analysis_main(["--root", root, "--rules", "determinism",
                        "--format", "json"])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert out["n_active"] > 0
    # default artifact path, resolved against --root
    assert os.path.isfile(
        os.path.join(root, "artifacts", "analysis", "report.json")
    )

    _write(root, "src/repro/serving/keys.py", GOOD_DETERMINISM)
    rc = analysis_main(["--root", root, "--rules", "determinism",
                        "--out", "-"])
    assert rc == 0
    assert "analysis: OK" in capsys.readouterr().out

    rc = analysis_main(["--root", root, "--rules", "no-such-rule",
                        "--out", "-"])
    assert rc == 2


def test_cli_list_rules(capsys):
    rc = analysis_main(["--list-rules"])
    assert rc == 0
    out = capsys.readouterr().out
    for rid in ALL_RULES:
        assert rid in out


def test_unknown_rule_raises():
    with pytest.raises(KeyError, match="no-such-rule"):
        run_analysis(REPO_ROOT, rules=["no-such-rule"])


# -- the gate: this repository must be clean -----------------------------

def test_repository_is_clean_under_all_rules():
    report = run_analysis(REPO_ROOT)
    assert sorted(report.rules) == sorted(rule_ids())
    active = [f.finding.location() for f in report.active]
    assert active == [], (
        "repo has non-exempted analysis findings:\n" + "\n".join(active)
    )
    assert not report.unused_exemptions, (
        "stale exemptions: " + ", ".join(
            f"{e.rule}@{e.path}" for e in report.unused_exemptions
        )
    )
    # every exemption that IS used carries a justification
    for f in report.findings:
        if f.exempted:
            assert f.justification.strip()


def test_repository_exemption_file_is_valid():
    ctx = RepoContext(REPO_ROOT)
    exemptions = load_exemptions(ctx, known_rules=rule_ids())
    assert exemptions, "repo exemption file should exist and have entries"
    for e in exemptions:
        assert e.justification.strip()
