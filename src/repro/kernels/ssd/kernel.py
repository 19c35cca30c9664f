"""Pallas SSD (state-space duality) kernel — Mamba-2's chunked scan.

Implements the SSD decomposition (Dao & Gu, arXiv:2405.21060): the sequence
is split into chunks of length L; within a chunk the recurrence is computed
as a (masked, decay-weighted) attention-like matmul (MXU work), and across
chunks only the (N×P) state is carried — giving O(S·L) work with O(N·P)
carried state instead of the O(S²) of attention.  This is what makes the
``long_500k`` shape feasible for mamba2/zamba2.

Recurrence (per batch b, head h, with group g = h // (H//G)):
    state_t = exp(A_h·dt_t)·state_{t-1} + dt_t · B_t ⊗ x_t        (N×P)
    y_t     = C_tᵀ·state_t + D_h·x_t

Chunked form computed by the kernel per chunk (cum = inclusive cumsum of
a_t = A_h·dt_t within the chunk; total = cum[L−1]):
    Y_intra = ((C Bᵀ) ⊙ exp(cum_i − cum_j) ⊙ dt_j ⊙ [i ≥ j]) @ X
    Y_inter = exp(cum) ⊙ (C @ state_prev)
    state   = exp(total)·state_prev + (B ⊙ dt·exp(total − cum))ᵀ @ X

TPU mapping: grid = (B, H, S/L) over heads-major blocks; the chunk axis is
innermost, so the fp32 (N×P) state lives in VMEM scratch across the
sequential chunk walk — the carried state never touches HBM (the same
locality the paper gets from keeping data in each FPGA's partition).  All
decays are ≤ 1 (A < 0, dt > 0), so exp() is numerically safe in fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, state_in_ref,
    y_ref, state_out_ref, state_ref,
    *, n_chunks: int, chunk: int,
):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = state_in_ref[0, 0]

    x = x_ref[0, 0].astype(jnp.float32)             # (L, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)       # (1, L)
    a_h = a_ref[hi]                                  # A_h (< 0), SMEM scalar
    bmat = b_ref[0, 0].astype(jnp.float32)          # (L, N)
    cmat = c_ref[0, 0].astype(jnp.float32)          # (L, N)
    d_skip = d_ref[hi]

    # Row (1, L) and column (L, 1) views of dt and of the inclusive cumsum
    # of A_h·dt, built with masked reductions: no vector transpose, no
    # scalar extraction, no scan primitive inside the kernel.
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = ii >= jj
    dt_col = jnp.sum(jnp.where(ii == jj, dt_row, 0.0), axis=1,
                     keepdims=True)                 # (L, 1)
    a_row = a_h * dt_row                            # (1, L)
    a_col = a_h * dt_col                            # (L, 1)
    cum_col = jnp.sum(jnp.where(lower, a_row, 0.0), axis=1,
                      keepdims=True)                # (L, 1)  sum_{j<=i}
    cum_row = jnp.sum(jnp.where(ii <= jj, a_col, 0.0), axis=0,
                      keepdims=True)                # (1, L)  sum_{i<=j}
    total = jnp.sum(a_row, axis=1, keepdims=True)   # (1, 1)

    # --- intra-chunk: masked decay-weighted "attention" ---
    seg = jnp.where(lower, cum_col - cum_row, NEG_INF)   # i>=j => <= 0
    scores = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (L, L) C_i · B_j
    weights = scores * jnp.exp(seg) * dt_row
    y = jax.lax.dot_general(
        weights, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (L, P)

    # --- inter-chunk: contribution of the carried state ---
    state = state_ref[...]                          # (N, P)
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        cmat, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # --- D skip connection ---
    y += d_skip * x

    # --- state update (overlappable with next chunk's intra work) ---
    decay_to_end = jnp.exp(total - cum_col) * dt_col     # (L, 1)
    state_ref[...] = jnp.exp(total) * state + jax.lax.dot_general(
        bmat * decay_to_end, x,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_out_ref[0, 0] = state_ref[...].astype(state_out_ref.dtype)


def ssd_pallas(
    x: jnp.ndarray,      # (B, S, H, P)
    dt: jnp.ndarray,     # (B, S, H)   positive
    a: jnp.ndarray,      # (H,)        negative
    b: jnp.ndarray,      # (B, S, G, N)
    c: jnp.ndarray,      # (B, S, G, N)
    d: jnp.ndarray,      # (H,)
    *,
    chunk: int = 128,
    interpret: bool = False,
    init_state: jnp.ndarray | None = None,   # (B, H, N, P) fp32
):
    """Returns (y: (B, S, H, P), final_state: (B, H, N, P) fp32).

    ``init_state`` seeds the carried (N×P) state (zeros when ``None``) —
    the chunk-fed entry point (``ops.ssd_chunk_fed``) threads each
    segment's final state into the next segment's scan through it.

    The kernel runs heads-major: x, B and C are transposed to
    ``(B, H|G, S, ·)`` and dt to ``(B, H, 1, S)`` so that the last two
    dims of every block are ``(chunk, P|N)`` or ``(1, chunk)`` — the
    (8, 128) tiling rule holds at any head count — and the per-head
    scalars ``a`` and ``d`` are read from SMEM.
    """
    bsz, s, h, p = x.shape
    _, _, g, n = b.shape
    assert h % g == 0, (h, g)
    assert s % chunk == 0, (s, chunk)
    hpg = h // g
    n_chunks = s // chunk
    if init_state is None:
        init_state = jnp.zeros((bsz, h, n, p), jnp.float32)
    assert init_state.shape == (bsz, h, n, p), init_state.shape

    xh = x.transpose(0, 2, 1, 3)                     # (B, H, S, P)
    dth = dt.transpose(0, 2, 1)[:, :, None, :]       # (B, H, 1, S)
    bh = b.transpose(0, 2, 1, 3)                     # (B, G, S, N)
    ch = c.transpose(0, 2, 1, 3)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    kernel = functools.partial(_ssd_kernel, n_chunks=n_chunks, chunk=chunk)
    yh, state = pl.pallas_call(
        kernel,
        grid=(bsz, h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            smem,
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // hpg, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // hpg, ci, 0)),
            smem,
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xh, dth, a.astype(jnp.float32), bh, ch, d.astype(jnp.float32),
      init_state.astype(jnp.float32))
    return yh.transpose(0, 2, 1, 3), state
