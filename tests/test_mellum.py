"""Mellum2's serving path on the CPU at its small ``smoke_config``
(two periods of three window layers and a full one; 8 experts, top-2),
against a plain reference written here from the published description:

* prefill and then decode through the two caches (the window layers'
  rings, the full layers' cache) give the reference's full-forward
  logits, with a prompt longer than the window so the ring wraps; the
  reference without YaRN, or without the window, does not;
* the expert share: the layer's outputs on four devices (each holding a
  quarter of the experts) sum to the whole layer's;
* dropless: with every token routed to the same experts, each
  assignment is computed.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model, moe

CFG = get_smoke_config("mellum2-12b")
F32 = jnp.float32


@pytest.fixture
def interpret(monkeypatch):
    """The Pallas kernels interpreted, as the models call them."""
    from repro.kernels import ops

    for name in ("flash_attention", "flash_decode", "moe_gmm"):
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(ops, name), interpret=True))


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta, yarn):
    """x (S, H, D): the two halves of each head rotated by position; with
    ``yarn`` (a ``YaRN``) the frequencies ramped between its two
    rotation counts and cos, sin scaled by its attention factor."""
    S, _, D = x.shape
    j = np.arange(D // 2)
    inv = theta ** (-2.0 * j / D)
    scale = 1.0
    if yarn is not None:
        def dim(rot):
            return D * math.log(yarn.original_max_position
                                / (rot * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(dim(yarn.beta_fast)), 0)
        high = min(math.ceil(dim(yarn.beta_slow)), D - 1)
        ramp = np.clip((j - low) / max(high - low, 1e-3), 0, 1)
        inv = inv / yarn.factor * ramp + inv * (1 - ramp)
        scale = yarn.attention_factor
    ang = np.arange(S)[:, None] * inv
    cos = jnp.asarray(scale * np.cos(ang), F32)[:, None]
    sin = jnp.asarray(scale * np.sin(ang), F32)[:, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _moe_ref(m, cfg, x):
    """Dense over the held experts, weighted by float32 top-k routing over
    all of the router's experts."""
    probs = jax.nn.softmax(x @ m["router"], -1)
    top, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    top = top / top.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)
    gate = gate[:, cfg.expert_offset:cfg.expert_offset + cfg.held_experts]
    h = jnp.einsum("sd,edf->esf", x, m["wi"])
    g = jnp.einsum("sd,edf->esf", x, m["wg"])
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * h, m["wo"])
    return jnp.einsum("esd,se->sd", out, gate)


def _layer_ref(w, cfg, x, *, window, yarn):
    a = w["attn"]
    S = x.shape[0]
    h = _rms(x, w["ln1"], cfg.norm_eps)
    q = _rms(jnp.einsum("sd,dhk->shk", h, a["wq"]), a["q_norm"],
             cfg.norm_eps)
    k = _rms(jnp.einsum("sd,dhk->shk", h, a["wk"]), a["k_norm"],
             cfg.norm_eps)
    v = jnp.einsum("sd,dhk->shk", h, a["wv"])
    q, k = _rope(q, cfg.rope_theta, yarn), _rope(k, cfg.rope_theta, yarn)
    pos = np.arange(S)
    mask = pos[:, None] >= pos[None, :]
    if window is not None:
        mask &= pos[:, None] - pos[None, :] < window
    G = cfg.num_heads // cfg.num_kv_heads
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    x = x + jnp.einsum("qhd,hdm->qm", jnp.einsum("hqk,khd->qhd", p, v),
                       a["wo"])
    return x + _moe_ref(w["moe"], cfg, _rms(x, w["ln2"], cfg.norm_eps))


def reference_logits(params, cfg, tokens, *, yarn=True, window=True):
    """(S, V) logits of one sequence, every position at once."""
    params = jax.tree.map(lambda a: a.astype(F32), params)
    x = params["embed"][tokens]
    dec = params["decoder"]
    for p in range(cfg.periods):
        for j in range(cfg.full_attn_every):
            full = j == cfg.full_attn_every - 1
            w = jax.tree.map(
                (lambda a: a[p]) if full else (lambda a: a[p, j]),
                dec["full" if full else "window"])
            x = _layer_ref(
                w, cfg, x,
                window=None if full or not window else cfg.sliding_window,
                yarn=cfg.full_attn_yarn if full and yarn else None)
    return _rms(x, params["final_norm"], cfg.norm_eps) @ params["unembed"]


# ---------------------------------------------------------------------------
# serving through both caches
# ---------------------------------------------------------------------------

B, PROMPT, STEPS = 2, 24, 6          # the prompt passes the 16-slot ring


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT + STEPS), 0,
                              CFG.vocab_size)


def _served(params, tokens, impl):
    """Logits of the prompt's last position, then of each decode step."""
    model = build_model(CFG, impl=impl)
    cache = model.init_cache(B, PROMPT + STEPS, dtype=F32)
    assert cache["kv_window"]["k"].shape[3] == CFG.sliding_window
    lg, cache = model.prefill(params, tokens[:, :PROMPT], cache, dtype=F32)
    out = [lg[:, -1]]
    for t in range(PROMPT, PROMPT + STEPS - 1):
        lg, cache = model.decode_step(params, tokens[:, t:t + 1], cache,
                                      dtype=F32)
        out.append(lg[:, -1])
    # every assignment of every token counted, in every layer
    want = (PROMPT + STEPS - 1) * B * CFG.experts_per_token
    np.testing.assert_array_equal(cache["moe_load"].sum(1), want)
    return jnp.stack(out, 1)                           # (B, STEPS, V)


def _gap(params, tokens, served, **ref):
    worst = 0.0
    for b in range(B):
        want = reference_logits(params, CFG, tokens[b, :-1], **ref)
        want = want[PROMPT - 1:, :CFG.vocab_size]
        worst = max(worst, float(jnp.abs(
            served[b, :, :CFG.vocab_size] - want).max()))
    return worst


# float32 throughout: the program and the reference differ by summation
# order alone (~1e-6 relative); 1e-3 leaves that room and is far below
# what a missing mechanism changes (checked below)
TOL = 1e-3


@pytest.mark.parametrize("impl", ["blockwise", "pallas"])
def test_prefill_then_decode_matches_reference(params, tokens, impl,
                                               request):
    if impl == "pallas":
        request.getfixturevalue("interpret")
    served = _served(params, tokens, impl)
    assert _gap(params, tokens, served) <= TOL


def test_pallas_decode_matches_blockwise_every_step(params, tokens,
                                                   interpret):
    """Two periods served past the prompt for two more wraps of the
    16-slot ring, float32: the pallas step gives the jnp path's logits,
    window rings and full cache at every step, each layer reading and
    writing its own slots of the stacked caches."""
    steps = 2 * CFG.sliding_window
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, PROMPT + steps), 0,
                              CFG.vocab_size)
    caches, step = {}, {}
    for impl in ("blockwise", "pallas"):
        model = build_model(CFG, impl=impl)
        _, caches[impl] = model.prefill(
            params, toks[:, :PROMPT],
            model.init_cache(B, PROMPT + steps, dtype=F32), dtype=F32)
        step[impl] = jax.jit(functools.partial(model.decode_step, dtype=F32))

    def close(t):
        for kind in ("kv_window", "kv_full"):
            for name in ("k", "v"):
                np.testing.assert_allclose(
                    caches["pallas"][kind][name],
                    caches["blockwise"][kind][name], atol=1e-4, rtol=1e-4,
                    err_msg=f"{kind} {name} at {t}")

    close(PROMPT)
    for t in range(PROMPT, PROMPT + steps):
        out = {}
        for impl in step:
            out[impl], caches[impl] = step[impl](params, toks[:, t:t + 1],
                                                 caches[impl])
        np.testing.assert_allclose(out["pallas"], out["blockwise"],
                                   atol=TOL, rtol=0, err_msg=f"step {t}")
        close(t)


@pytest.mark.parametrize("drop", ["yarn", "window"])
def test_reference_without_a_mechanism_disagrees(params, tokens, drop):
    served = _served(params, tokens, "blockwise")
    assert _gap(params, tokens, served, **{drop: False}) > 30 * TOL


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def _moe_params(key, cfg):
    from repro.models.base import init_params

    return init_params(moe.moe_blueprint(cfg), key)


def _share(cfg, p, offset, held):
    c = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
    q = dict(p, **{k: p[k][offset:offset + held]
                   for k in ("wi", "wg", "wo")})
    return c, q


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_expert_shares_sum_to_the_layer(impl, request):
    if impl == "pallas":
        request.getfixturevalue("interpret")
    p = _moe_params(jax.random.PRNGKey(2), CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, CFG.d_model), F32)
    h = CFG.num_experts // 4
    total, load = 0.0, []
    for i in range(4):
        c, q = _share(CFG, p, i * h, h)
        y, n = moe.moe_serve(q, c, x, impl=impl)
        total, load = total + y, load + [n]
    whole = _moe_ref(p, CFG, x.reshape(-1, CFG.d_model)).reshape(x.shape)
    np.testing.assert_allclose(total, whole, atol=1e-4, rtol=1e-4)
    # the shares' counts are the whole layer's: k per token
    assert int(jnp.concatenate(load).sum()) == 40 * CFG.experts_per_token


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_serving_layer_drops_nothing(impl, request):
    """Every token routed to experts 0 and 1: 24 times an expert's even
    share of the assignments, which the capacity layer cuts to 1.25."""
    if impl == "pallas":
        request.getfixturevalue("interpret")
    p = _moe_params(jax.random.PRNGKey(4), CFG)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(10.0) \
        .at[:, 1].set(5.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5),
                                  (2, 48, CFG.d_model), F32))
    y, load = moe.moe_serve(p, CFG, x, impl=impl)
    assert load.tolist() == [96, 96] + [0] * (CFG.num_experts - 2)
    want = _moe_ref(p, CFG, x.reshape(-1, CFG.d_model)).reshape(x.shape)
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
    # the capacity layer loses whole tokens' outputs
    dropped, _ = moe.moe_apply(p, CFG, x)
    lost = jnp.all(dropped == 0, axis=-1) & jnp.any(want != 0, axis=-1)
    assert int(lost.sum()) > 0
