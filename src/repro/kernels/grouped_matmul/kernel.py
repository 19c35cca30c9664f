"""Pallas grouped matmul for dropless MoE prefill: ``moe_gmm``.

The rows of ``lhs`` are (token, expert) pairs sorted by expert, each
expert's rows starting at a multiple of ``tile_m`` (the gaps are zero
rows), so every ``tile_m``-row tile belongs to one expert.  Grid step
``(j, i)`` multiplies row tile ``i`` by column block ``j`` of its
expert's weight matrix, with the whole contraction in one block: an
expert's weight block stays in VMEM across all of its row tiles, so each
held expert's weights are read once per column block.

Two scalar-prefetched arrays steer the pipeline: ``tile_group`` (the
expert of each row tile) and ``n_used`` (the tiles that hold rows).  Past
``n_used`` every block index stays on the last used tile, so no block is
fetched or written again and no product is computed: the work follows
the routed rows, not the static worst-case extent.  Rows of unused tiles
are left unwritten.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernel's name, as its device events show it
NAME = "moe_gmm"
#: scoped VMEM the kernel may use (v5e has 128 MiB per core)
VMEM_LIMIT = 64 * 1024 * 1024


def _moe_gmm_kernel(tile_group_ref, n_used_ref, lhs_ref, rhs_ref, out_ref):
    del tile_group_ref

    @pl.when(pl.program_id(1) < n_used_ref[0])
    def _():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[0],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def moe_gmm_pallas(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   tile_group: jnp.ndarray, n_used: jnp.ndarray, *,
                   tile_m: int, tile_n: int, out_dtype=None,
                   interpret: bool = False) -> jnp.ndarray:
    """lhs (M, K), rhs (G, K, N), tile_group (M // tile_m,) int32,
    n_used (1,) int32 -> (M, N): row tile ``i < n_used`` is
    ``lhs[tile] @ rhs[tile_group[i]]`` with float32 accumulation."""
    m, k = lhs.shape
    g, k2, n = rhs.shape
    assert k == k2, (lhs.shape, rhs.shape)
    assert m % tile_m == 0 and n % tile_n == 0, (m, n, tile_m, tile_n)
    out_dtype = out_dtype or lhs.dtype

    def last(i, nu):
        return jnp.minimum(i, jnp.maximum(nu[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tile_n, m // tile_m),
        in_specs=[
            pl.BlockSpec((tile_m, k), lambda j, i, tg, nu: (last(i, nu), 0)),
            pl.BlockSpec((1, k, tile_n),
                         lambda j, i, tg, nu: (tg[last(i, nu)], 0, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n),
                               lambda j, i, tg, nu: (last(i, nu), j)),
    )
    return pl.pallas_call(
        _moe_gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(tile_group, n_used, lhs, rhs)
