"""Bring-up check: the main path runs on one TPU chip and agrees with its
references.

    python chip_smoke.py [--seed N]

One process, four phases in order; the first that fails ends the run with
a nonzero exit and no result line.

(a) device   — JAX must see a TPU; there is no CPU branch.
(b) kernels  — the four Pallas kernels, compiled, at served-model widths,
               against their ``repro.kernels.ref`` oracles.
(c) replica  — command-r-35b's serving step (``build_model(cfg,
               impl="pallas")``, the calls examples/serve_llm.py makes) at
               its published widths with the depth cut to 4 layers: 4
               requests prefill 512-token prompts, then 32 decode steps.
               Prefill and every decode step's logits are compared
               with ``impl="blockwise"`` on the same parameters and
               tokens.
(d) matrix   — 16 deployment cells through
               ``ScenarioSuite.from_spec(...).run(engine="jax")`` (the
               path of ``launch/serve.py --sweep --engine jax``), each
               checked against ``engine="vector"`` on the host.

Times printed along the way are information only.  The last line of
standard output is the result, as JSON, with the device JAX reports.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# libtpu logs under /tmp unless told otherwise; this run writes only
# inside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from repro.compile_cache import use_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# (atol, rtol) of each kernel against its oracle.  bf16 outputs: a few
# bf16 ulps (2^-8 relative) of O(1) values.  The f32 scan: 64 sequential
# steps of two roundings each drift by at most ~64·2·2^-24 ≈ 8e-6
# relative, kept under 1e-4 whatever order XLA fuses the oracle in.
KERNEL_TOL = {
    "flash_attention": (2e-2, 2e-2),
    "flash_decode": (2e-2, 2e-2),
    "selective_scan": (1e-4, 1e-4),
    "moe_gmm": (2e-2, 2e-2),
}

# replica step (phase c)
MODEL = "command-r-35b"
LAYERS = 4
BATCH = 4
PROMPT = 512
DECODE_STEPS = 32


# Pallas vs blockwise logits, as ||pallas - blockwise|| / ||blockwise||.
# The paths round activations to bf16 (2^-9 relative) in different places
# and the TPU rounds f32 matmul operands to bf16 by default, so over 4
# layers they should differ by about 1e-2 or less; a wrong head group,
# mask or cache slot differs by O(1).
LOGITS_REL_TOL = 5e-2


class PhaseFailed(SystemExit):
    def __init__(self, phase: str, msg: str) -> None:
        super().__init__(f"FAIL ({phase}) {msg}")


def require(ok: bool, phase: str, msg: str) -> None:
    if not ok:
        raise PhaseFailed(phase, msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def compile_checked(phase: str, name: str, fn, *args, kernel=True):
    """Compile ``fn`` for the chip; a Pallas path must hold its kernel."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    if kernel:
        require("tpu_custom_call" in compiled.as_text(), phase,
                f"{name}: no tpu_custom_call in the compiled program")
    return compiled, dt


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def phase_device(cache_dir: str) -> dict:
    devs = jax.devices()
    d = devs[0]
    require(d.platform == "tpu", "a",
            f"JAX found no TPU: platform {d.platform!r} "
            f"({len(devs)} device(s))")
    say("a", f"device {d.device_kind} x{len(devs)}, jax {jax.__version__}, "
             f"compile cache {cache_dir}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# (b) kernels
# ---------------------------------------------------------------------------


def _close(name: str, got, want) -> None:
    atol, rtol = KERNEL_TOL[name]
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, "b",
            f"{name}: shape {got.shape} != oracle {want.shape}")
    require(bool(np.isfinite(got).all()), "b", f"{name}: non-finite output")
    err = np.abs(got - want)
    bad = err > atol + rtol * np.abs(want)
    require(not bad.any(), "b",
            f"{name}: {int(bad.sum())} elements beyond atol {atol} rtol "
            f"{rtol} of the oracle (max abs err {err.max():.3g})")
    say("b", f"{name} {got.shape} matches ref (max abs err "
             f"{err.max():.3g}, atol {atol} rtol {rtol})")


def phase_kernels(seed: int) -> None:
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(shape, dtype=bf16, scale=1.0):
        x = jax.random.normal(next(keys), shape, f32) * scale
        return x.astype(dtype)

    # flash attention: command-r-35b prefill, B1 S512 H64 Kv8 D128
    q = normal((1, 512, 64, 128))
    k = normal((1, 512, 8, 128))
    v = normal((1, 512, 8, 128))
    fa, _ = compile_checked(
        "b", "flash_attention",
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        q, k, v,
    )
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
    ).transpose(0, 2, 1, 3)
    _close("flash_attention", fa(q, k, v), want)

    # flash decode: B4 over a 4096-token cache, ragged occupancy, read at
    # layer 2 of a 3-layer head-major stack
    S = 4096
    q = normal((4, 1, 64, 128))
    kc = normal((3, 4, 8, S, 128))
    vc = normal((3, 4, 8, S, 128))
    lens = jnp.array([S, 3000, 1234, 1])
    valid = jnp.arange(S)[None, :] < lens[:, None]
    layer = jnp.int32(2)
    fd, _ = compile_checked(
        "b", "flash_decode",
        lambda q, k, v, i, m: ops.flash_decode(q, k, v, i, kv_valid=m,
                                               interpret=False),
        q, kc, vc, layer, valid,
    )
    want = ref.flash_decode_ref(q[:, 0], kc[2], vc[2], valid)[:, None]
    _close("flash_decode", fd(q, kc, vc, layer, valid), want)

    # selective scan: falcon-mamba-7b, chunk 64 of d_inner 8192, state 16
    a = jax.nn.sigmoid(normal((1, 64, 8192, 16), f32))
    b = normal((1, 64, 8192, 16), f32, 0.1)
    h0 = normal((1, 8192, 16), f32)
    ss, _ = compile_checked(
        "b", "selective_scan",
        lambda a, b, h: ops.selective_scan(a, b, h, interpret=False),
        a, b, h0,
    )
    _close("selective_scan", ss(a, b, h0), ref.selective_scan_ref(a, b, h0))

    # grouped matmul: qwen3-moe-30b experts, 128 groups of 128 rows,
    # D2048 F768
    x = normal((128 * 128, 2048))
    w = normal((128, 2048, 768), scale=2048 ** -0.5)
    sizes = jnp.full((128,), 128, jnp.int32)
    gm, _ = compile_checked(
        "b", "moe_gmm",
        lambda x, w, g: ops.moe_gmm(x, w, g, block_m=128, interpret=False),
        x, w, sizes,
    )
    _close("moe_gmm", gm(x, w, sizes), ref.moe_gmm_ref(x, w, sizes))


# ---------------------------------------------------------------------------
# (c) replica step
# ---------------------------------------------------------------------------


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def phase_replica(seed: int) -> None:
    from repro.configs import get_config
    from repro.models import build_model

    full = get_config(MODEL)
    cfg = dataclasses.replace(full, num_layers=LAYERS)
    say("c", f"{MODEL}: depth cut to {LAYERS} of {full.num_layers} layers; "
             f"published widths d_model {cfg.d_model}, heads "
             f"{cfg.num_heads}/{cfg.num_kv_heads} kv, head_dim "
             f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
             f"{cfg.vocab_size}")

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        build_model(cfg).init(jax.random.PRNGKey(seed), jnp.bfloat16)
    )
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    say("c", f"bf16 parameters drawn from seed {seed}: {nbytes / 1e9:.2f} GB "
             f"in {time.perf_counter() - t0:.1f} s")

    prompts = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (BATCH, PROMPT), 0, cfg.vocab_size
    ).astype(jnp.int32)
    tok0 = jnp.zeros((BATCH, 1), jnp.int32)
    V = cfg.vocab_size
    prefill, decode, caches, logits = {}, {}, {}, {}
    for impl in ("blockwise", "pallas"):
        model = build_model(cfg, impl=impl)
        cache = model.init_cache(BATCH, PROMPT + DECODE_STEPS)
        pallas = impl == "pallas"
        prefill[impl], c_pre = compile_checked(
            "c", f"{impl} prefill", model.prefill, params, prompts, cache,
            kernel=pallas,
        )
        decode[impl], c_dec = compile_checked(
            "c", f"{impl} decode", model.decode_step, params, tok0, cache,
            kernel=pallas,
        )
        (lg, caches[impl]), t_pre = _timed(prefill[impl], params, prompts,
                                           cache)
        logits[impl] = lg[..., :V]
        say("c", f"{impl}: compile prefill {c_pre:.1f} s + decode "
                 f"{c_dec:.1f} s; prefill {BATCH}x{PROMPT} tokens "
                 f"{t_pre * 1e3:.1f} ms")
    _logits_match("prefill", logits)

    # pallas picks each next token; blockwise decodes the same tokens on
    # its own cache, and every step's logits are compared
    steps, worst = [], 0.0
    for step in range(DECODE_STEPS):
        tok = jnp.argmax(logits["pallas"], -1).astype(jnp.int32)
        for impl in ("blockwise", "pallas"):
            (lg, caches[impl]), dt = _timed(decode[impl], params, tok,
                                            caches[impl])
            logits[impl] = lg[..., :V]
            if impl == "pallas":
                steps.append(dt)
        rel = _logits_match(f"decode step {step + 1}", logits,
                            quiet=step > 0)
        worst = max(worst, rel)
    for impl, cache in caches.items():
        require(int(cache["len"]) == PROMPT + DECODE_STEPS, "c",
                f"{impl} cache holds {int(cache['len'])} tokens after "
                f"{DECODE_STEPS} decode steps")
    say("c", f"pallas logits match blockwise at all {DECODE_STEPS} decode "
             f"steps: worst relative L2 diff {worst:.4g} <= "
             f"{LOGITS_REL_TOL}; pallas decode {np.mean(steps[1:]) * 1e3:.2f}"
             f" ms/step after the first ({steps[0] * 1e3:.1f} ms)")


def _logits_match(what: str, logits: dict, quiet: bool = False) -> float:
    """Relative L2 difference of pallas from blockwise logits, checked
    against ``LOGITS_REL_TOL``."""
    want = np.asarray(logits["blockwise"], np.float32)
    got = np.asarray(logits["pallas"], np.float32)
    require(bool(np.isfinite(got).all()), "c",
            f"pallas {what} logits not finite")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    require(rel <= LOGITS_REL_TOL, "c",
            f"pallas {what} logits differ from blockwise by {rel:.4g} "
            f"relative > {LOGITS_REL_TOL}")
    if not quiet:
        say("c", f"pallas {what} logits {got.shape} match blockwise: "
                 f"relative L2 diff {rel:.4g} <= {LOGITS_REL_TOL} (max abs "
                 f"diff {np.abs(got - want).max():.4g} of max |logit| "
                 f"{np.abs(want).max():.4g})")
    return rel


# ---------------------------------------------------------------------------
# (d) deployment matrix
# ---------------------------------------------------------------------------


def matrix_spec() -> dict:
    """command-r-35b on g5.48xlarge, request mode: {spothedge,
    even_spread} x {aws-1, aws-3} x seeds 0-3, 1 simulated hour each,
    Poisson 1 req/s, timeout 60 s, concurrency 4.  Span sampling is off:
    the jax engine records spans of single-attempt requests only, so
    sampled span counts are not an engine-parity quantity."""
    from benchmarks.jax_engine import _spec

    spec = _spec(n_seeds=4, hours=1.0)
    spec["name"] = "chip-smoke"
    spec["model"] = MODEL
    spec["sweep"]["traces"] = ["aws-1", "aws-3"]
    spec["observability"] = {"trace_sample": 0.0}
    return spec


def phase_matrix() -> None:
    from benchmarks.jax_engine import _cells_match, _strip_wall
    from repro.experiments import ScenarioSuite
    from repro.serving.jaxengine.engine import FALLBACK_COUNTER

    suite = ScenarioSuite.from_spec(matrix_spec())
    require(len(suite) == 16, "d", f"matrix has {len(suite)} cells, not 16")
    t0 = time.perf_counter()
    rep_j = suite.run(engine="jax")
    t_jax = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep_v = suite.run(engine="vector")
    t_vec = time.perf_counter() - t0
    counters = (rep_j.metrics or {}).get("counters", {})
    fallbacks = {k: v for k, v in counters.items()
                 if k.startswith(FALLBACK_COUNTER)}
    require(not fallbacks, "d", f"lanes fell back to NumPy: {fallbacks}")
    cj, cv = _strip_wall(rep_j.cells), _strip_wall(rep_v.cells)
    same = [_cells_match([a], [b]) for a, b in zip(cj, cv)]
    bad = [c.cell_id for c, ok in zip(rep_j.cells, same) if not ok]
    require(not bad, "d",
            f"{len(bad)}/{len(same)} cells differ from the vector engine: "
            f"{bad}")
    say("d", f"{sum(same)}/{len(same)} cells equal to the vector engine, "
             f"0 NumPy fallback lanes; jax {t_jax:.1f} s (compile "
             f"included), vector {t_vec:.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args(argv)
    cache_dir = use_compile_cache()
    device = phase_device(cache_dir)
    phase_kernels(args.seed)
    phase_replica(args.seed)
    phase_matrix()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
