"""Weights drawn from the seed, and the plain reference of the
``moe_replica`` configuration's forward pass: a decoder of window and full
attention layers whose every FFN is a mixture of experts, of which the
chip holds a share.

The configuration file carries the published ``config.json``'s keys; the
program's sizes are read from them here (``shape``).  The weights are the
benchmark's own, drawn as ``reference/lm.py`` draws them: every leaf, and
every layer of a stacked leaf, from its own key folded out of the seed, in
float32, served as its bfloat16 rounding; each expert from a key of its
global index, so a share holds the same experts whatever its neighbours.

The reference (``logits``) is the decoder as the configuration states it,
in ``jax.numpy`` at float32 with matmuls at the highest precision, one
sequence at a time, every position at once, layer by layer:

* pre-RMSNorm attention: q, k and v projections, RMSNorm of each head of
  q and k, rotary embeddings on the two halves of each head (theta from
  ``rope_parameters``; on full layers YaRN as transformers computes it,
  with truncation, cos and sin scaled by its attention factor),
  grouped-query causal attention, within ``sliding_window`` positions on
  sliding layers;
* pre-RMSNorm MoE: router logits over all ``router_experts`` in float32,
  softmax, the top ``num_experts_per_tok`` renormalised; the held experts
  ``[expert_offset, expert_offset + num_experts)`` computed densely on
  every position (SwiGLU) and summed with those weights (zero where an
  expert is not among a position's top ones); no capacity, nothing
  dropped;
* a final RMSNorm and the untied output head.

``precision="fp8"`` is the control: every matmul operand rounded to
float8 e4m3 under a per-tensor scale, as in ``reference/lm.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from reference.lm import _key_parts, _mm, _nest, _rms, _seed_parts

SLIDING, FULL = "sliding_attention", "full_attention"

# layer leaf -> the index its key is folded from
_LAYER = {
    ("ln1",): 10,
    ("ln2",): 11,
    ("attn", "wq"): 12,
    ("attn", "wk"): 13,
    ("attn", "wv"): 14,
    ("attn", "wo"): 15,
    ("attn", "q_norm"): 16,
    ("attn", "k_norm"): 17,
    ("moe", "router"): 18,
    ("moe", "wi"): 19,
    ("moe", "wg"): 20,
    ("moe", "wo"): 21,
}
_NORMS = {("ln1",), ("ln2",), ("attn", "q_norm"), ("attn", "k_norm"),
          ("final_norm",)}
_EXPERTS = {("moe", "wi"), ("moe", "wg"), ("moe", "wo")}
# leading axes that make a projection's fan-in
_FAN_IN_AXES = {("attn", "wo"): 2}


def shape(c: Dict) -> Dict:
    """The program's sizes, read from the configuration's keys."""
    types = c["layer_types"]
    period = types.index(FULL) + 1
    L = c["num_hidden_layers"]
    if (len(types) != L or L % period
            or types != ([SLIDING] * (period - 1) + [FULL]) * (L // period)):
        raise ValueError(f"layer_types is not whole periods of "
                         f"{period - 1} sliding layers and a full one")
    rope = c["rope_parameters"]
    theta = rope[SLIDING]["rope_theta"]
    full = rope[FULL]
    if full["rope_theta"] != theta or full["rope_type"] != "yarn":
        raise ValueError("full layers need YaRN over the sliding theta")
    return {
        "layers": L, "period": period, "d": c["hidden_size"],
        "heads": c["num_attention_heads"], "kv": c["num_key_value_heads"],
        "hd": c["head_dim"], "f": c["moe_intermediate_size"],
        "vocab": c["vocab_size"], "window": c["sliding_window"],
        "experts": c["router_experts"], "top_k": c["num_experts_per_tok"],
        "held": c["num_experts"], "offset": c["expert_offset"],
        "theta": theta, "eps": c["rms_norm_eps"],
        "yarn": (full["factor"], full["original_max_position_embeddings"],
                 full["beta_fast"], full["beta_slow"],
                 full["attention_factor"]),
        "embed_std": c["embed_std"],
    }


def _frozen(c: Dict):
    """The sizes as a hashable static argument."""
    return tuple(sorted(shape(c).items()))


def _layer_shapes(s: Dict) -> Dict[Tuple[str, ...], Tuple[int, ...]]:
    d, h, kv, hd, f = s["d"], s["heads"], s["kv"], s["hd"], s["f"]
    return {
        ("ln1",): (d,), ("ln2",): (d,),
        ("attn", "wq"): (d, h, hd),
        ("attn", "wk"): (d, kv, hd),
        ("attn", "wv"): (d, kv, hd),
        ("attn", "wo"): (h, hd, d),
        ("attn", "q_norm"): (hd,), ("attn", "k_norm"): (hd,),
        ("moe", "router"): (d, s["experts"]),
        ("moe", "wi"): (d, f), ("moe", "wg"): (d, f), ("moe", "wo"): (f, d),
    }


def _draw(key, path, shp, embed_std: float) -> jax.Array:
    """One leaf in float32, rounded to bfloat16."""
    if path in _NORMS:
        x = 1.0 + 0.1 * jax.random.normal(key, shp, jnp.float32)
    elif path == ("embed",):
        x = embed_std * jax.random.normal(key, shp, jnp.float32)
    else:
        fan_in = math.prod(shp[:_FAN_IN_AXES.get(path, 1)])
        x = jax.random.normal(key, shp, jnp.float32) / math.sqrt(fan_in)
    return x.astype(jnp.bfloat16)


def _layer(s: Dict, base, layer) -> Dict:
    """One layer's weights, bfloat16, keyed by leaf path: the held
    experts stacked in order."""
    held = jnp.arange(s["held"]) + s["offset"]
    out = {}
    for path, shp in _layer_shapes(s).items():
        key = jax.random.fold_in(jax.random.fold_in(base, _LAYER[path]),
                                 layer)
        if path in _EXPERTS:
            out[path] = jax.vmap(lambda e: _draw(
                jax.random.fold_in(key, e), path, shp, s["embed_std"]))(held)
        else:
            out[path] = _draw(key, path, shp, s["embed_std"])
    return out


def _outer(s: Dict, base):
    """The embedding, the output head and the final norm, bfloat16."""
    d, v = s["d"], s["vocab"]
    return (_draw(jax.random.fold_in(base, 1), ("embed",), (v, d),
                  s["embed_std"]),
            _draw(jax.random.fold_in(base, 3), ("unembed",), (d, v),
                  s["embed_std"]),
            _draw(jax.random.fold_in(base, 2), ("final_norm",), (d,),
                  s["embed_std"]))


@functools.partial(jax.jit, static_argnums=(0,))
def _serving_params_jit(fs, seed_parts):
    s = dict(fs)
    base = _key_parts(seed_parts)
    P, n = s["layers"] // s["period"], s["period"] - 1
    layer = jnp.arange(s["layers"]).reshape(P, s["period"])
    window = jax.vmap(jax.vmap(lambda i: _layer(s, base, i)))(layer[:, :n])
    full = jax.vmap(lambda i: _layer(s, base, i))(layer[:, n])
    embed, unembed, norm = _outer(s, base)
    return {"embed": embed, "unembed": unembed, "final_norm": norm,
            "decoder": {"window": _nest(window), "full": _nest(full)}}


def serving_params(c: Dict, seed: int) -> Dict:
    """Every weight, bfloat16, in the program's parameter layout."""
    return _serving_params_jit(_frozen(c), _seed_parts(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_jit(fs, seed_parts, layer):
    w = _layer(dict(fs), _key_parts(seed_parts), layer)
    return _nest({k: v.astype(jnp.float32) for k, v in w.items()})


@functools.partial(jax.jit, static_argnums=(0,))
def _outer_jit(fs, seed_parts):
    return tuple(x.astype(jnp.float32)
                 for x in _outer(dict(fs), _key_parts(seed_parts)))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------


def _rope(x, theta, yarn):
    """x: (S, H, D); rotate the two halves of D by position.  ``yarn``:
    (factor, original positions, beta_fast, beta_slow, attention factor)
    or None."""
    s, _, dh = x.shape
    half = dh // 2
    j = jnp.arange(half, dtype=jnp.float32)
    inv = 1.0 / (theta ** (2 * j / dh))
    scale = 1.0
    if yarn is not None:
        factor, orig, fast, slow, scale = yarn

        def dim(rot):   # the dimension whose wavelength fits ``rot`` turns
            return dh * math.log(orig / (rot * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(dim(fast)), 0)
        high = min(math.ceil(dim(slow)), dh - 1)
        ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = (inv / factor) * ramp + inv * (1.0 - ramp)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos = scale * jnp.cos(ang)[:, None, :]
    sin = scale * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer_fwd(fs, x, w, full, low):
    s = dict(fs)
    h, kv, hd, eps = s["heads"], s["kv"], s["hd"], s["eps"]
    n = x.shape[0]
    a = w["attn"]
    hn = _rms(x, w["ln1"], eps)
    yarn = s["yarn"] if full else None
    q = _rope(_rms(_mm("sd,dhk->shk", hn, a["wq"], low), a["q_norm"], eps),
              s["theta"], yarn)
    k = _rope(_rms(_mm("sd,dhk->shk", hn, a["wk"], low), a["k_norm"], eps),
              s["theta"], yarn)
    v = _mm("sd,dhk->shk", hn, a["wv"], low)
    pos = jnp.arange(n)
    mask = pos[:, None] >= pos[None, :]
    if not full:
        mask &= pos[:, None] - pos[None, :] < s["window"]
    g = h // kv

    def group(j):
        qj = jax.lax.dynamic_slice_in_dim(q, j * g, g, axis=1)   # (n, g, hd)
        sc = _mm("qgd,kd->gqk", qj, k[:, j], low) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return _mm("gqk,kd->qgd", p, v[:, j], low)

    o = jax.lax.map(group, jnp.arange(kv))                     # (kv,n,g,hd)
    o = o.transpose(1, 0, 2, 3).reshape(n, h, hd)
    x = x + _mm("shk,hkd->sd", o, a["wo"], low)

    m = w["moe"]
    hn = _rms(x, w["ln2"], eps)
    probs = jax.nn.softmax(_mm("sd,de->se", hn, m["router"], low), axis=-1)
    top, idx = jax.lax.top_k(probs, s["top_k"])
    top = top / top.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[pos[:, None], idx].set(top)
    gate = gate[:, s["offset"]:s["offset"] + s["held"]]          # (n, held)
    up = _mm("sd,edf->esf", hn, m["wi"], low)
    act = jax.nn.silu(_mm("sd,edf->esf", hn, m["wg"], low)) * up
    out = _mm("esf,efd->esd", act, m["wo"], low)
    return x + jnp.einsum("esd,se->sd", out, gate,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(fs, x, unembed, norm, low):
    return _mm("sd,dv->sv", _rms(x, norm, dict(fs)["eps"]), unembed, low)


def logits(c: Dict, seed: int, tokens, precision: str = "f32") -> jax.Array:
    """(S, V) float32 logits of one token sequence at every position."""
    low = {"f32": False, "fp8": True}[precision]
    fs = _frozen(c)
    s = dict(fs)
    parts = _seed_parts(seed)
    embed, unembed, norm = _outer_jit(fs, parts)
    x = embed[jnp.asarray(tokens)]
    for layer in range(s["layers"]):
        w = _layer_jit(fs, parts, layer)
        x = _layer_fwd(fs, x, w, (layer + 1) % s["period"] == 0, low)
        del w
    return _head(fs, x, unembed, norm, low)
