"""Weights drawn from the seed, and the plain reference of the replica
configuration's forward pass.

The weights are the benchmark's own: every leaf, and every layer of a
stacked leaf, is drawn from its own key folded out of the seed, in float32,
and served as its bfloat16 rounding.  ``serving_params`` draws them all on
the device in one jitted call; ``layer_weights`` draws one layer again, so
the reference can go layer by layer without holding the model twice.

The reference (``logits``) is the decoder as the configuration file states
it, in ``jax.numpy`` at float32 with matmuls at the highest precision:
pre-RMSNorm, a parallel block (attention and SwiGLU read the same normed
input and are added to the residual), rotary embeddings on the two halves
of each head, grouped-query causal attention, a final RMSNorm and logits
against the tied embedding.  No kernel, cache or batching: one sequence at
a time, every position at once.

``precision="fp8"`` is the control: every matmul operand (weights and
activations) rounded to float8 e4m3 under a per-tensor scale, the step
below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# layer leaf -> the index its key is folded from.  Projections are
# N(0, 1/fan_in); the embedding, whose transpose is the output head, is
# N(0, embed_std^2) with the configuration's ``embed_std``; norms are
# 1 + 0.1 N(0, 1).
_LAYER = {
    ("ln1",): 10,
    ("attn", "wq"): 11,
    ("attn", "wk"): 12,
    ("attn", "wv"): 13,
    ("attn", "wo"): 14,
    ("mlp", "wi"): 15,
    ("mlp", "wg"): 16,
    ("mlp", "wo"): 17,
}


# leading axes that make a projection's fan-in (the output projection of
# attention reads every head)
_FAN_IN_AXES = {("attn", "wo"): 2}


def _dims(c: Dict) -> Tuple[int, int, int, int, int, int]:
    return (c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"],
            c["d_ff"], c["vocab_size"])


def _layer_shapes(c: Dict) -> Dict[Tuple[str, ...], Tuple[int, ...]]:
    d, h, kv, hd, f, _ = _dims(c)
    return {
        ("ln1",): (d,),
        ("attn", "wq"): (d, h, hd),
        ("attn", "wk"): (d, kv, hd),
        ("attn", "wv"): (d, kv, hd),
        ("attn", "wo"): (h, hd, d),
        ("mlp", "wi"): (d, f),
        ("mlp", "wg"): (d, f),
        ("mlp", "wo"): (f, d),
    }


def _draw(key, path: Tuple[str, ...], shape, embed_std: float
          ) -> jax.Array:
    """One leaf in float32, rounded to bfloat16."""
    if path in (("ln1",), ("final_norm",)):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif path == ("embed",):
        x = embed_std * jax.random.normal(key, shape, jnp.float32)
    else:
        fan_in = math.prod(shape[:_FAN_IN_AXES.get(path, 1)])
        x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return x.astype(jnp.bfloat16)


def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


def _seed_parts(seed: int) -> jax.Array:
    """A seed of up to 64 bits as two 32-bit words."""
    return jnp.asarray([seed % (2**32), seed // (2**32)], jnp.uint32)


def _key_parts(parts) -> jax.Array:
    return jax.random.fold_in(jax.random.key(parts[0]), parts[1])


def _frozen(c: Dict):
    """The configuration's sizes as a hashable static argument."""
    keys = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
            "vocab_size", "num_layers", "embed_std", "rope_theta",
            "norm_eps")
    return tuple((k, c[k]) for k in keys)


def _layer(c: Dict, base, layer) -> Dict:
    """One layer's weights, bfloat16, keyed by leaf path."""
    return {path: _draw(jax.random.fold_in(
                jax.random.fold_in(base, _LAYER[path]), layer),
                path, shape, c["embed_std"])
            for path, shape in _layer_shapes(c).items()}


def _outer(c: Dict, base) -> Tuple[jax.Array, jax.Array]:
    """The embedding and the final norm, bfloat16."""
    d, _, _, _, _, v = _dims(c)
    return (_draw(jax.random.fold_in(base, 1), ("embed",), (v, d),
                  c["embed_std"]),
            _draw(jax.random.fold_in(base, 2), ("final_norm",), (d,),
                  c["embed_std"]))


@functools.partial(jax.jit, static_argnums=(0,))
def _serving_params_jit(fc, seed_parts):
    c = dict(fc)
    base = _key_parts(seed_parts)
    stacked = jax.vmap(lambda layer: _layer(c, base, layer))(
        jnp.arange(c["num_layers"]))
    embed, norm = _outer(c, base)
    return {"embed": embed, "final_norm": norm, "decoder": _nest(stacked)}


def serving_params(c: Dict, seed: int) -> Dict:
    """Every weight, bfloat16, in the program's parameter layout."""
    return _serving_params_jit(_frozen(c), _seed_parts(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_jit(fc, seed_parts, layer):
    w = _layer(dict(fc), _key_parts(seed_parts), layer)
    return _nest({k: v.astype(jnp.float32) for k, v in w.items()})


def layer_weights(c: Dict, seed: int, layer: int) -> Dict:
    """Layer ``layer``'s weights again, as float32 values of the served
    bfloat16 ones."""
    return _layer_jit(_frozen(c), _seed_parts(seed), layer)


@functools.partial(jax.jit, static_argnums=(0,))
def _outer_jit(fc, seed_parts):
    return tuple(x.astype(jnp.float32)
                 for x in _outer(dict(fc), _key_parts(seed_parts)))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under a per-tensor scale, back to float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, low: bool):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, D); rotate the two halves of D by position."""
    s, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_fwd(fc, x, w, low):
    c = dict(fc)
    _, h, kv, hd, _, _ = _dims(c)
    s = x.shape[0]
    hn = _rms(x, w["ln1"], c["norm_eps"])
    q = _rope(_mm("sd,dhk->shk", hn, w["attn"]["wq"], low), c["rope_theta"])
    k = _rope(_mm("sd,dhk->shk", hn, w["attn"]["wk"], low), c["rope_theta"])
    v = _mm("sd,dhk->shk", hn, w["attn"]["wv"], low)
    g = h // kv
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(j):
        qj = jax.lax.dynamic_slice_in_dim(q, j * g, g, axis=1)   # (s, g, hd)
        kj, vj = k[:, j], v[:, j]                                  # (s, hd)
        sc = _mm("qgd,kd->gqk", qj, kj, low) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return _mm("gqk,kd->qgd", p, vj, low)

    o = jax.lax.map(group, jnp.arange(kv))                     # (kv,s,g,hd)
    o = o.transpose(1, 0, 2, 3).reshape(s, h, hd)
    attn = _mm("shk,hkd->sd", o, w["attn"]["wo"], low)
    up = _mm("sd,df->sf", hn, w["mlp"]["wi"], low)
    gate = _mm("sd,df->sf", hn, w["mlp"]["wg"], low)
    ffn = _mm("sf,fd->sd", jax.nn.silu(gate) * up, w["mlp"]["wo"], low)
    return x + attn + ffn


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(fc, x, embed, norm, low):
    c = dict(fc)
    return _mm("sd,vd->sv", _rms(x, norm, c["norm_eps"]), embed, low)


def logits(c: Dict, seed: int, tokens, precision: str = "f32") -> jax.Array:
    """(S, V) float32 logits of one token sequence at every position."""
    low = {"f32": False, "fp8": True}[precision]
    fc = _frozen(c)
    embed, norm = _outer_jit(fc, _seed_parts(seed))
    x = embed[jnp.asarray(tokens)]
    for layer in range(c["num_layers"]):
        w = layer_weights(c, seed, layer)
        x = _layer_fwd(fc, x, w, low)
        del w
    return _head(fc, x, embed, norm, low)
