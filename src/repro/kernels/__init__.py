"""Pallas TPU kernels for the data-plane hot spots.

Each kernel ships three artifacts:

* ``<name>.py``  — the ``pl.pallas_call`` + explicit BlockSpec VMEM tiling,
* ``ops.py``     — jit'd wrappers with model-layout transforms and the
                   ``interpret`` switch (compiled by default; True runs
                   the kernel body in Python, which is how the CPU tests
                   validate it),
* ``ref.py``     — pure-jnp oracles the tests ``assert_allclose`` against.

``compat.py`` holds the Pallas TPU names of the pinned JAX
(``CompilerParams``, the VMEM handle); kernels never touch
``jax.experimental.pallas.tpu`` symbols directly.

Kernels:

* ``flash_attention``  — prefill attention (online softmax, causal /
  sliding-window block skipping, GQA via index_map head folding).
* ``flash_decode``     — one-query-token attention vs. a long KV cache,
  blocked over KV with running max/denominator.
* ``selective_scan``   — Mamba-1 within-chunk recurrence h' = a·h + b.
* ``moe_gmm``          — grouped matmul over ragged per-expert row groups
  (a scalar-prefetched tile→expert table picks each tile's weights).

TPU tiling notes: MXU wants the two minor dims in multiples of (8, 128)
for fp32 / (16, 128) for bf16; every BlockSpec here keeps each of its
two minor dims a multiple of that tile or equal to the whole array dim,
the two shapes the TPU compiler accepts (``tests/test_tpu_compile.py``
compiles each kernel for a v5e at served-model widths).
"""

from repro.kernels import compat, ops, ref

__all__ = ["compat", "ops", "ref"]
