"""The chip path compiles for a TPU v5e, with no chip attached.

Each case lowers and compiles one program of the main path for one device
of a described ``v5e:2x2`` topology: the four Pallas kernels at the widths
of the served models, the served steps of two models, and the scenario
engine's phase-B kernel under x64.
A compile that passes is not a chip run, but it catches what interpret
mode cannot: blocks not aligned to the tiling, kernels that overflow
scoped VMEM, programs the TPU compiler refuses.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (S, window, prefix_len) at command-r-35b widths: H64 Kv8 D128
ATTN_CASES = [
    (512, None, 0),
    (300, None, 0),        # S not a multiple of the 128 block
    (512, None, 64),       # prefix-LM zone
    (512, 128, 0),         # sliding window
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_compiles(one_chip, case):
    S, window, prefix = case
    q = _sds(one_chip, (1, S, 64, 128))
    kv = _sds(one_chip, (1, S, 8, 128))
    txt = _compile_text(
        lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, window=window, prefix_len=prefix,
            interpret=False,
        ),
        q, kv, kv,
    )
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles_at_replica_prefill(one_chip):
    """replica.prefill's call, B4 over 8192 positions at command-r-35b's
    heads, under the tiles the kernel picks (bq 256, bkv 512): the folded
    2048-row score tile fits the kernel's scoped VMEM, as one kernel."""
    import re

    B, S = 4, 8192
    txt = _compile_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        _sds(one_chip, (B, S, 64, 128)),
        _sds(one_chip, (B, S, 8, 128)),
        _sds(one_chip, (B, S, 8, 128)),
    )
    calls = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
                       txt)
    assert len(calls) == 1 and calls[0].startswith("flash_attention."), calls


def test_flash_decode_compiles(one_chip):
    """B4 over a 4096-token cache, command-r-35b heads (G = 8), read at
    layer 3 of a 5-layer stack."""
    B, S = 4, 4096
    txt = _compile_text(
        lambda q, k, v, layer, m: ops.flash_decode(
            q, k, v, layer, kv_valid=m, interpret=False
        ),
        _sds(one_chip, (B, 1, 64, 128)),
        _sds(one_chip, (5, B, 8, S, 128)),
        _sds(one_chip, (5, B, 8, S, 128)),
        _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (B, S), jnp.bool_),
    )
    assert "tpu_custom_call" in txt


def _cache_shaped(txt: str, stacks) -> list:
    """(name, opcode, operands) of each instruction of the compiled
    program, outside fused computations, whose result holds a whole
    cache stack of ``stacks`` or one layer of it: the same dimensions in
    any order, ones dropped.  Instructions that move nothing (parameters,
    tuples and their elements, bitcasts) are left out."""
    import re

    def dims(shape):
        return tuple(sorted(d for d in shape if d != 1))

    wanted = {dims(s) for s in stacks} | {dims(s[1:]) for s in stacks}
    found, fused = [], False
    for line in txt.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) .*\{$", line)
        if head:
            fused = head.group(2).startswith("fused")
            continue
        m = re.match(r"\s+(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                     r"([\w-]+)\((.*)", line)
        if fused or not m or m.group(3) in (
                "parameter", "get-tuple-element", "tuple", "bitcast"):
            continue
        if dims(int(d) for d in m.group(2).split(",") if d) in wanted:
            found.append((m.group(1), m.group(3), m.group(4)))
    return found


@pytest.mark.parametrize("arch", ["command-r-35b", "mellum2-12b"])
def test_decode_step_leaves_the_cache_where_it_lies(one_chip, arch):
    """The served decode step (smoke config, ``impl="pallas"``, the cache
    donated as the benchmark's drivers donate it) touches its cache stacks
    only through one in-place ``dynamic-update-slice`` of K and one of V
    per kind of layer, which each ``flash_decode`` call reads: no copy,
    slice, transpose or restack of a layer's cache or of a whole stack.
    The head width (128) and window (1024) are the published ones and the
    cache is serving-sized, so the stacks stay in HBM as when served (the
    compiler moves small ones to VMEM whole)."""
    import dataclasses
    import re

    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_smoke_config(arch), head_dim=128)
    if cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=1024)
    model = build_model(cfg, impl="pallas")
    params, cache = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        (model.abstract(jnp.bfloat16), model.abstract_cache(32, 8192)),
    )
    stacks = [c["k"].shape for n, c in cache.items() if n.startswith("kv")]
    txt = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, _sds(one_chip, (32, 1), jnp.int32), cache).compile().as_text()
    found = _cache_shaped(txt, stacks)
    writes = [name for name, op, _ in found if op == "dynamic-update-slice"]
    assert len(writes) == 2 * len(stacks), found
    assert [f for f in found if f[1] != "dynamic-update-slice"] == [], found
    reads = re.findall(r"%flash_decode\.\d+ = .*? custom-call\(([^)]*)\)",
                       txt)
    assert len(reads) == len(stacks)
    for operands in reads:
        assert sum(f"%{w}," in operands + "," for w in writes) == 2, operands


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_model_step_names_its_kernels(one_chip, mode):
    """The served step (smoke command-r, ``impl="pallas"``) compiles each
    kernel call to a custom call named after the kernel
    (``flash_attention.N``, ``flash_decode.N``) whose scope lies under the
    layer scan's ``attention``: the names a profile's readings match."""
    import re

    from repro.configs import get_smoke_config
    from repro.models import build_model

    model = build_model(get_smoke_config("command-r-35b"), impl="pallas")
    params, cache = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        (model.abstract(jnp.bfloat16), model.abstract_cache(2, 136)),
    )
    if mode == "prefill":
        kernel, fn, S = "flash_attention", model.prefill, 128
    else:
        kernel, fn, S = "flash_decode", model.decode_step, 1
    txt = _compile_text(fn, params, _sds(one_chip, (2, S), jnp.int32), cache)
    calls = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"'
                       r'[^\n]*op_name="([^"]*)"', txt)
    assert calls
    for name, scope in calls:
        assert re.fullmatch(rf"{kernel}\.\d+", name), name
        assert "/layers/" in scope and "/attention/" in scope, scope


# (Q, C, N): falcon-mamba-7b (d_inner 8192, state 16) at the scan chunk
# of the kernel table and of the model, zamba2-7b (d_inner 7168, state 64)
SCAN_CASES = [(64, 8192, 16), (256, 8192, 16), (64, 7168, 64)]


@pytest.mark.parametrize("case", SCAN_CASES)
def test_selective_scan_compiles(one_chip, case):
    Q, C, N = case
    f32 = jnp.float32
    txt = _compile_text(
        lambda a, b, h: ops.selective_scan(a, b, h, interpret=False),
        _sds(one_chip, (1, Q, C, N), f32),
        _sds(one_chip, (1, Q, C, N), f32),
        _sds(one_chip, (1, C, N), f32),
    )
    assert "tpu_custom_call" in txt


def test_moe_gmm_compiles(one_chip):
    """qwen3-moe-30b expert widths: 128 groups of 128 rows, D2048 F768."""
    txt = _compile_text(
        lambda x, w, g: ops.moe_gmm(x, w, g, block_m=128, interpret=False),
        _sds(one_chip, (128 * 128, 2048)),
        _sds(one_chip, (128, 2048, 768)),
        _sds(one_chip, (128,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("rows,block_m", [(384, 16), (131072, 512)])
def test_moe_gmm_compiles_at_mellum_widths(one_chip, rows, block_m):
    """The held share of mellum2 (16 experts, D2304 F896 and back) at a
    decode step's and a prefill chunk's rows, padded for 16 groups: one
    ``moe_gmm.N`` custom call each, no padded weights."""
    import re

    from repro.kernels import moe_gmm as gmm_mod

    M = gmm_mod.padded_rows(rows, 16, block_m)
    for D, F in ((2304, 896), (896, 2304)):
        txt = _compile_text(
            lambda x, w, g: ops.moe_gmm(x, w, g, block_m=block_m,
                                        interpret=False),
            _sds(one_chip, (M, D)),
            _sds(one_chip, (16, D, F)),
            _sds(one_chip, (16,), jnp.int32),
        )
        calls = re.findall(
            r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', txt)
        assert len(calls) == 1 and calls[0].startswith("moe_gmm."), calls
        assert not re.search(r"\[16,\d+,\d+\]\S* pad\(", txt)


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_mellum_step_compiles(one_chip, mode):
    """The smoke mellum2's pallas step, prefill past its window: each
    kernel is a custom call named after it, ``moe_gmm.N`` (three a layer
    kind) under ``ffn/moe/experts`` and the attention kernel under
    ``attention``, all inside ``window`` or ``full``."""
    import re

    from repro.configs import get_smoke_config
    from repro.models import build_model

    model = build_model(get_smoke_config("mellum2-12b"), impl="pallas")
    params, cache = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        (model.abstract(jnp.bfloat16), model.abstract_cache(2, 40)),
    )
    if mode == "prefill":
        fn, S, attention = model.prefill, 32, "flash_attention"
    else:
        fn, S, attention = model.decode_step, 1, "flash_decode"
    txt = _compile_text(fn, params, _sds(one_chip, (2, S), jnp.int32), cache)
    calls = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"'
                       r'[^\n]*op_name="([^"]*)"', txt)
    kinds = {}
    for name, scope in calls:
        kernel = name.rsplit(".", 1)[0]
        assert kernel in (attention, "moe_gmm"), name
        kind = "window" if "/window/" in scope else "full"
        assert f"/{kind}/" in scope and "/layers/" in scope, scope
        if kernel == "moe_gmm":
            assert "/ffn/moe/experts/" in scope, scope
        else:
            assert "/attention/" in scope, scope
        kinds.setdefault((kernel, kind), 0)
        kinds[(kernel, kind)] += 1
    assert kinds == {(attention, "window"): 1, (attention, "full"): 1,
                     ("moe_gmm", "window"): 3, ("moe_gmm", "full"): 3}


def test_phase_b_kernel_compiles_under_x64(one_chip):
    """The scenario engine's vmapped scan at a small shape signature."""
    from repro.serving.jaxengine import kernel as K

    key = K.KernelKey(G=240, W=24, N=360, R=4, Q=32, C=4, NREG=2, E=2,
                      AMAX=3, ATYP=2, lb_rr=False, expire_on=True)
    L = 2
    f64, i64 = jnp.float64, jnp.int64
    with jax.enable_x64(True):
        shapes = [
            _sds(one_chip, (L, key.N), f64),               # arr
            _sds(one_chip, (L, key.N), f64),               # svc
            _sds(one_chip, (L, key.N), i64),               # rcode
            _sds(one_chip, (L, key.R, key.NREG), f64),     # rtt
            _sds(one_chip, (L, key.W, key.R), jnp.bool_),  # ready
            _sds(one_chip, (L, key.E), i64),               # kill_slot
            _sds(one_chip, (L, key.E), i64),               # kill_g
            _sds(one_chip, (L,), f64),                     # timeout
            _sds(one_chip, (key.G,), f64),                 # ts
            _sds(one_chip, (key.G,), i64),                 # gs
            _sds(one_chip, (key.G,), i64),                 # wins
        ]
        compiled = K._build_kernel(key).lower(*shapes).compile()
    assert compiled.memory_analysis() is not None
