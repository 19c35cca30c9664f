"""Public wrapper of the grouped matmul: the tile metadata from the group
sizes, the column block, and the interpret-mode fallback on the CPU."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import should_interpret
from repro.kernels.grouped_matmul.kernel import moe_gmm_pallas

#: rows per tile; every group's rows start at a multiple of it
TILE_M = 128
#: VMEM the double-buffered blocks of one call may take
_BLOCK_BUDGET = 24 * 1024 * 1024


def column_block(k: int, n: int, tile_m: int, itemsize: int) -> int:
    """The widest column block (``n`` itself, or a multiple of 128 that
    divides it) whose double-buffered lhs, rhs and out blocks fit the
    budget."""
    cands = [n] + [c for c in range(n - n % 128, 127, -128)
                   if c < n and n % c == 0]
    for c in cands:
        if 2 * (tile_m * k + k * c + tile_m * c) * itemsize <= _BLOCK_BUDGET:
            return c
    return cands[-1]


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def moe_gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
            *, tile_m: int = TILE_M, interpret: bool | None = None
            ) -> jnp.ndarray:
    """Grouped matmul: rows ``[o_g, o_g + group_sizes[g])`` of ``lhs``
    (M, K), with ``o_g`` the sum of the earlier sizes, times ``rhs[g]``
    (K, N).  Every size is a multiple of ``tile_m``, as is M; rows past
    the sum of the sizes are left unwritten."""
    if interpret is None:
        interpret = should_interpret()
    m, k = lhs.shape
    n = rhs.shape[-1]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32)) // tile_m
    tiles = jnp.arange(m // tile_m, dtype=jnp.int32)
    tile_group = jnp.minimum(jnp.searchsorted(ends, tiles, side="right"),
                             rhs.shape[0] - 1).astype(jnp.int32)
    n_used = ends[-1:]
    tile_n = column_block(k, n, tile_m, lhs.dtype.itemsize)
    return moe_gmm_pallas(lhs, rhs, tile_group, n_used, tile_m=tile_m,
                          tile_n=tile_n, interpret=interpret)
