"""jit'd wrapper for flash attention: padding, CPU interpret fallback, and
the gradient rule that lets the Pallas forward serve training too."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import should_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def _pad_seq(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    pad = (-x.shape[2]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, block_q, block_kv, interpret):
    sq = q.shape[2]
    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_kv), _pad_seq(v, block_kv)
    out = flash_attention_pallas(
        qp, kp, vp,
        causal=causal, window=window, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return out[:, :, :sq, :]


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_kv, interpret):
    out = _flash(q, k, v, causal, window, scale, block_q, block_kv, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, scale, block_q, block_kv, interpret, res, g):
    # The backward is the VJP of the blockwise jnp attention on the same
    # inputs: the same function (online softmax, same masks), recomputed
    # in f32 and differentiated by JAX.
    from repro.models.layers import blockwise_attention

    q, k, v = res
    _, vjp = jax.vjp(
        functools.partial(blockwise_attention, causal=causal, window=window,
                          scale=scale),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Flash attention over (B, H, S, D) tensors with GQA kv (B, Hkv, S, D).

    Sequence lengths are padded to block multiples; because padding keys are
    *future* positions under the causal mask (and windowed mask), they are
    invisible to real queries, and padded query rows are cropped.
    For non-causal use, padded kv would attend — so we require causal or
    explicit full blocks there (asserted).

    Differentiable: the gradient is that of
    :func:`repro.models.layers.blockwise_attention` on the same inputs.
    """
    if interpret is None:
        interpret = should_interpret()
    sq, skv = q.shape[2], k.shape[2]
    if not causal:
        assert sq % block_q == 0 and skv % block_kv == 0, (
            "non-causal attention requires block-aligned sequence lengths "
            f"(got {sq=}, {skv=})")
    return _flash(q, k, v, causal, window, scale, block_q, block_kv,
                  interpret)
