"""Composable pure-JAX model layers for every assigned architecture family.

No flax — parameters are plain pytrees (dicts of arrays), every layer is an
``init_*(cfg, key) -> params`` / ``apply(params, x, ...) -> y`` pair, and
layer stacks are ``lax.scan`` over stacked parameter pytrees so compile time
is O(1) in depth (96-layer nemotron compiles as fast as 4-layer whisper).

Attention/SSD have three interchangeable implementations selected by
``cfg.attn_impl``:

* ``pallas`` — the TPU kernels from ``repro.kernels`` (target hardware);
* ``jnp``    — blockwise flash-style scans in pure jnp: same asymptotic
  FLOPs/bytes, bounded memory, compiles on any backend — this is what the
  512-device dry-run lowers so ``cost_analysis`` reflects the real
  algorithm, not an interpreter;
* ``ref``    — the materialized oracle (tests only).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _init(key, shape, dtype, scale: float = 0.02):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def resolve_attn_impl(cfg: ModelConfig) -> str:
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(cfg: ModelConfig, key, dim: Optional[int] = None) -> Params:
    del key
    return {"scale": jnp.ones((dim or cfg.d_model,), _dtype(cfg))}


def rms_norm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def init_layernorm(cfg: ModelConfig, key, dim: Optional[int] = None) -> Params:
    del key
    d = dim or cfg.d_model
    return {"scale": jnp.ones((d,), _dtype(cfg)),
            "bias": jnp.zeros((d,), _dtype(cfg))}


def layer_norm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def init_norm(cfg: ModelConfig, key, dim: Optional[int] = None) -> Params:
    if cfg.family == "encdec":
        return init_layernorm(cfg, key, dim)
    return init_rmsnorm(cfg, key, dim)


def apply_norm(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    if "bias" in params:
        return layer_norm(params, x, cfg.norm_eps)
    return rms_norm(params, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor, ``0.1·m·ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(dim: int, theta: float, scaling) -> tuple:
    """YaRN's (low, high) frequency indices: the dims that turn ``beta``
    times over the original context, ``dim·ln(L/(2π·beta)) / (2 ln
    theta)``, floored at ``beta_fast`` and ceiled at ``beta_slow``."""
    ys = dict(scaling)
    n_ctx = ys["original_max_position_embeddings"]

    def dim_at(beta):
        return dim * math.log(n_ctx / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_at(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_at(ys["beta_slow"])), dim - 1)
    return low, high


def rope_freqs(dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies of the (dim/2) rotated pairs; with a YaRN
    ``scaling``, a linear ramp from the plain frequencies (below ``low``)
    to the plain ones over ``factor`` (above ``high``)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return freqs
    low, high = yarn_range(dim, theta, scaling)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / dict(scaling)["factor"] * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None, interleave: bool = False) -> jnp.ndarray:
    """x: (..., S, D) with D even; positions: (S,) or broadcastable.

    ``interleave`` rotates the pairs (2i, 2i+1) (the input is first
    de-interleaved into evens then odds, and the result keeps that
    layout); ``scaling`` is a YaRN ``rope_scaling`` (``rope_freqs``), whose
    cos and sin carry ``mscale(mscale) / mscale(mscale_all_dim)``."""
    d = x.shape[-1]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    freqs = rope_freqs(d, theta, scaling)              # (D/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (S, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        ys = dict(scaling)
        m = (yarn_mscale(ys["factor"], ys.get("mscale", 1.0))
             / yarn_mscale(ys["factor"], ys.get("mscale_all_dim", 0.0)))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention core — blockwise jnp flash (dry-run / CPU path) + dispatch
# ---------------------------------------------------------------------------


def _block_ranges(sq: int, skv: int, q_chunk: int, kv_chunk: int,
                  causal: bool, window: Optional[int], skip: bool,
                  offset: Optional[int] = None):
    """Static kv-block range visible to each q block."""
    n_q = -(-sq // q_chunk)
    n_kv = -(-skv // kv_chunk)
    if offset is None:
        offset = skv - sq  # decode/prefill alignment: q row i is abs pos offset+i
    out = []
    for i in range(n_q):
        lo, hi = 0, n_kv
        if skip:
            row_hi = offset + min((i + 1) * q_chunk, sq) - 1
            row_lo = offset + i * q_chunk
            if causal:
                hi = min(hi, row_hi // kv_chunk + 1)
            if window is not None:
                lo = max(lo, (row_lo - window + 1) // kv_chunk)
        out.append((i, lo, max(lo + 1, hi)))
    return out


def blockwise_attention(
    q: jnp.ndarray,       # (B, Hq, Sq, Dk)
    k: jnp.ndarray,       # (B, Hkv, Skv, Dk)
    v: jnp.ndarray,       # (B, Hkv, Skv, Dv)
    *,
    causal: bool,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_chunk: int = 2048,
    kv_chunk: int = 2048,
    causal_skip: bool = True,
    q_offset: Optional[int] = None,
) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure jnp.

    Outer loop over q chunks is a static python loop so each q chunk scans
    only its *visible* kv range (``causal_skip``: drops the ~2× wasted FLOPs
    a dense causal mask pays — a measured lever in EXPERIMENTS §Perf); inner
    loop is ``lax.scan`` over kv chunks with running (m, l, acc).

    ``q_offset`` pins q row 0 to an explicit absolute position instead of
    the default right-aligned ``skv - sq`` convention — the chunked-prefill
    path (``models/prefill.py``) attends a mid-sequence chunk of rows
    against a full-length K/V scratch, so row ``i`` sits at ``q_offset + i``
    with valid keys only in ``[0, q_offset + sq)``.  Keys at or beyond the
    written prefix are excluded by the causal mask alone, and masked kv
    blocks are exact no-ops of the online softmax (``alpha == 1``, zero
    contributions), which is what keeps a chunked pass bit-identical to the
    bulk pass per row.
    """
    b, hq, sq, dk = q.shape
    _, hkv, skv, _ = k.shape
    dv = v.shape[-1]
    group = hq // hkv
    scale = scale if scale is not None else dk ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    offset = skv - sq if q_offset is None else q_offset

    qg = q.reshape(b, hkv, group, sq, dk).astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    pad_q = (-sq) % q_chunk
    if pad_q:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pad_q), (0, 0)))
    pad_kv = (-skv) % kv_chunk
    if pad_kv:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    n_kv = kf.shape[2] // kv_chunk
    kb = kf.reshape(b, hkv, n_kv, kv_chunk, dk)
    vb = vf.reshape(b, hkv, n_kv, kv_chunk, dv)

    outs = []
    for (i, lo, hi) in _block_ranges(sq, skv, q_chunk, kv_chunk, causal,
                                     window, causal_skip, offset):
        qi = lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, axis=3)
        rows = offset + i * q_chunk + jnp.arange(q_chunk)

        def step(carry, inp):
            m, l, acc = carry
            kj, vj, jidx = inp
            cols = jidx * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qi, kj)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            mask &= (cols < skv)[None, :]                     # kv padding
            if causal:
                mask &= cols[None, :] <= rows[:, None]
            if window is not None:
                mask &= cols[None, :] > rows[:, None] - window
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.where(s <= -1e29, 0.0, jnp.exp(s - m_new))
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bkgqc,bkcd->bkgqd", p, vj)
            return (m_new, l_new, acc_new), None

        # Carry inits derived arithmetically from qi so their varying-axes
        # type matches the scan body under shard_map manual axes (an
        # explicit lax.pcast would do the same but its transpose lowers to
        # an all-reduce variant that crashes XLA-CPU's AllReducePromotion
        # pass at 512 devices — see EXPERIMENTS.md §Perf notes).
        zero_col = jax.lax.stop_gradient(qi[..., :1]) * 0.0
        m0 = zero_col - 1e30
        l0 = zero_col
        a0 = zero_col * jnp.zeros((dv,), jnp.float32)
        span = hi - lo
        ks = lax.dynamic_slice_in_dim(kb, lo, span, axis=2)
        vs = lax.dynamic_slice_in_dim(vb, lo, span, axis=2)
        (m, l, acc), _ = lax.scan(
            step, (m0, l0, a0),
            (jnp.moveaxis(ks, 2, 0), jnp.moveaxis(vs, 2, 0),
             lo + jnp.arange(span)),
        )
        l = jnp.where(l == 0.0, 1.0, l)
        outs.append(acc / l)
    out = jnp.concatenate(outs, axis=3)[..., :sq, :]
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def attention_core(
    cfg: ModelConfig,
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None, q_offset: Optional[int] = None,
) -> jnp.ndarray:
    impl = resolve_attn_impl(cfg)
    aligned = q_offset is None or q_offset == k.shape[2] - q.shape[2]
    if impl == "pallas" and q.shape[-1] == v.shape[-1] and aligned:
        from repro.kernels.flash_attention import flash_attention
        from repro.models.shardctx import attention_runner

        run = attention_runner() or flash_attention
        return run(q, k, v, causal=causal, window=window, scale=scale)
    if impl == "ref" and aligned:
        from repro.kernels.flash_attention.ref import attention_ref

        return attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    # mid-sequence q offsets (chunked prefill) only exist in the blockwise
    # path — the kernels keep the right-aligned convention
    return blockwise_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
        causal_skip=cfg.causal_block_skip, q_offset=q_offset,
    )


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key) -> Params:
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    depth_scale = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    return {
        "wq": _init(ks[0], (cfg.d_model, cfg.n_heads * hd), dt),
        "wk": _init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd), dt),
        "wv": _init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd), dt),
        "wo": _init(ks[3], (cfg.n_heads * hd, cfg.d_model), dt, depth_scale),
    }


def attention(
    cfg: ModelConfig, params: Params, x: jnp.ndarray,
    positions: jnp.ndarray, *, causal: bool = True,
    kv_override: Optional[tuple] = None,
    return_kv: bool = False,
):
    """x: (B, S, D) -> (B, S, D).  ``kv_override`` supplies precomputed
    (k, v, kv_positions) for cross-attention (whisper decoder).
    ``return_kv`` additionally returns the (roped) K/V — the prefill path's
    cache source."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    xc = x.astype(_cdtype(cfg))
    q = jnp.einsum("bsd,dh->bsh", xc, params["wq"].astype(_cdtype(cfg)))
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    if kv_override is None:
        k = jnp.einsum("bsd,dh->bsh", xc, params["wk"].astype(_cdtype(cfg)))
        v = jnp.einsum("bsd,dh->bsh", xc, params["wv"].astype(_cdtype(cfg)))
        k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        if cfg.family != "encdec":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v, _ = kv_override
    out = attention_core(cfg, q, k, v, causal=causal, window=cfg.window)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * hd)
    y = jnp.einsum("bsh,hd->bsd", out,
                   params["wo"].astype(_cdtype(cfg))).astype(x.dtype)
    if return_kv:
        return y, (k, v)
    return y


def cross_kv(cfg: ModelConfig, params: Params, enc_out: jnp.ndarray):
    """Precompute encoder K/V for the whisper decoder's cross-attention."""
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    ec = enc_out.astype(_cdtype(cfg))
    k = jnp.einsum("bsd,dh->bsh", ec, params["wk"].astype(_cdtype(cfg)))
    v = jnp.einsum("bsd,dh->bsh", ec, params["wv"].astype(_cdtype(cfg)))
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    return k, v, jnp.arange(s)


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (minicpm3)
# ---------------------------------------------------------------------------


def init_mla(cfg: ModelConfig, key) -> Params:
    """MLA weights; ``q_lora_rank`` 0 takes the query straight from the
    input (``w_q``) instead of through a normed low-rank ``w_dq``/``w_uq``."""
    dt = _dtype(cfg)
    ks = jax.random.split(key, 8)
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    depth_scale = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    if cfg.q_lora_rank:
        q = {"w_dq": _init(ks[0], (cfg.d_model, cfg.q_lora_rank), dt),
             "q_norm": {"scale": jnp.ones((cfg.q_lora_rank,), dt)},
             "w_uq": _init(ks[1], (cfg.q_lora_rank, h * (dn + dr)), dt)}
    else:
        q = {"w_q": _init(ks[0], (cfg.d_model, h * (dn + dr)), dt)}
    return {
        **q,
        "w_dkv": _init(ks[2], (cfg.d_model, cfg.kv_lora_rank + dr), dt),
        "kv_norm": {"scale": jnp.ones((cfg.kv_lora_rank,), dt)},
        "w_uk": _init(ks[3], (cfg.kv_lora_rank, h * dn), dt),
        "w_uv": _init(ks[4], (cfg.kv_lora_rank, h * dv), dt),
        "wo": _init(ks[5], (h * dv, cfg.d_model), dt, depth_scale),
    }


def mla_query(cfg: ModelConfig, params: Params,
              xc: jnp.ndarray) -> jnp.ndarray:
    """(..., D) compute-dtype input -> (..., H, dn + dr) queries."""
    cd = _cdtype(cfg)
    if cfg.q_lora_rank:
        q_lat = rms_norm(params["q_norm"], xc @ params["w_dq"].astype(cd),
                         cfg.norm_eps)
        q = q_lat @ params["w_uq"].astype(cd)
    else:
        q = xc @ params["w_q"].astype(cd)
    return q.reshape(xc.shape[:-1] + (cfg.n_heads,
                                      cfg.qk_nope_dim + cfg.qk_rope_dim))


def mla_rope(cfg: ModelConfig, x: jnp.ndarray,
             positions: jnp.ndarray) -> jnp.ndarray:
    """The rotary embedding of MLA's rope part, with the config's YaRN
    scaling and pair layout."""
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling,
                      cfg.rope_interleave)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(dn + dr)^-1/2``, times ``mscale(mscale_all_dim)^2`` under YaRN."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_scaling is not None:
        ys = dict(cfg.rope_scaling)
        if ys.get("mscale_all_dim"):
            scale *= yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return scale


def mla_attention(
    cfg: ModelConfig, params: Params, x: jnp.ndarray, positions: jnp.ndarray,
    return_cache: bool = False,
):
    """Train/prefill path: expand the latent to per-head K/V (compute-rich),
    attend with the shared rope key appended.  ``return_cache`` also returns
    (c_kv latent (B,S,r), k_rope (B,S,dr)) — the MLA cache contents."""
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    cd = _cdtype(cfg)
    xc = x.astype(cd)

    q = mla_query(cfg, params, xc)                        # (B, S, H, dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = mla_rope(cfg, q_rope.transpose(0, 2, 1, 3), positions)

    dkv = xc @ params["w_dkv"].astype(cd)                 # (B, S, r + dr)
    c_kv = rms_norm(params["kv_norm"], dkv[..., :r], cfg.norm_eps)
    k_rope = mla_rope(cfg, dkv[..., r:][:, None], positions)  # (B,1,S,dr)
    with jax.named_scope("mla.absorb"):
        k_nope = (c_kv @ params["w_uk"].astype(cd)).reshape(b, s, h, dn)
        vfull = (c_kv @ params["w_uv"].astype(cd)).reshape(b, s, h, dv)

    qh = jnp.concatenate(
        [q_nope.transpose(0, 2, 1, 3), q_rope], axis=-1
    )                                                     # (B, H, S, dn+dr)
    kh = jnp.concatenate(
        [k_nope.transpose(0, 2, 1, 3),
         jnp.broadcast_to(k_rope, (b, h, s, dr))], axis=-1
    )
    vh = vfull.transpose(0, 2, 1, 3)
    with jax.named_scope("mla.attend"):
        out = attention_core(cfg, qh, kh, vh, causal=True,
                             scale=mla_softmax_scale(cfg))
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    y = (out @ params["wo"].astype(cd)).astype(x.dtype)
    if return_cache:
        return y, (c_kv, k_rope[:, 0])   # (B,S,r), (B,S,dr)
    return y


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def _act(name: str, x: jnp.ndarray) -> jnp.ndarray:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu2":
        r = jnp.maximum(x, 0.0)
        return r * r
    raise ValueError(name)


def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Params:
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    f = d_ff or cfg.d_ff
    depth_scale = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    p = {
        "w_up": _init(ks[0], (cfg.d_model, f), dt),
        "w_down": _init(ks[1], (f, cfg.d_model), dt, depth_scale),
    }
    if cfg.gated_mlp:
        p["w_gate"] = _init(ks[2], (cfg.d_model, f), dt)
    return p


def mlp(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    cd = _cdtype(cfg)
    xc = x.astype(cd)
    up = xc @ params["w_up"].astype(cd)
    if cfg.gated_mlp:
        up = _act(cfg.activation, xc @ params["w_gate"].astype(cd)) * up
    else:
        up = _act(cfg.activation, up)
    return (up @ params["w_down"].astype(cd)).astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE (capacity-based scatter dispatch, per batch row ⇒ data-partitionable)
# ---------------------------------------------------------------------------


def init_moe(cfg: ModelConfig, key) -> Params:
    """The router over all ``n_experts`` and the weights of the
    ``n_local_experts`` held here, plus the shared experts as one MLP."""
    dt = _dtype(cfg)
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_local_experts, cfg.d_model, cfg.d_ff
    depth_scale = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    p = {
        "router": _init(ks[0], (d, cfg.n_experts), jnp.float32),
        "w_up": _init(ks[1], (e, d, f), dt),
        "w_down": _init(ks[2], (e, f, d), dt, depth_scale),
    }
    if cfg.gated_mlp:
        p["w_gate"] = _init(ks[3], (e, d, f), dt)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(cfg, ks[4], d_ff=cfg.d_ff * cfg.n_shared_experts)
    return p


def _expert_ffn(cfg: ModelConfig, params: Params, xe: jnp.ndarray) -> jnp.ndarray:
    """xe: (..., E, C, D) -> (..., E, C, D), batched over experts."""
    cd = _cdtype(cfg)
    up = jnp.einsum("...ecd,edf->...ecf", xe, params["w_up"].astype(cd))
    if cfg.gated_mlp:
        gate = jnp.einsum("...ecd,edf->...ecf", xe, params["w_gate"].astype(cd))
        up = _act(cfg.activation, gate) * up
    else:
        up = _act(cfg.activation, up)
    return jnp.einsum("...ecf,efd->...ecd", up, params["w_down"].astype(cd))


def moe_topk(cfg: ModelConfig, router: jnp.ndarray, xc: jnp.ndarray):
    """Softmax over all ``n_experts`` router outputs (float32) and the
    greedy top-k: ``(weights (B,S,K), idx (B,S,K))``.  The weights are
    renormalised to sum to 1 only under ``norm_topk_prob``."""
    logits = jnp.einsum("bsd,de->bse", xc.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = lax.top_k(probs, cfg.experts_per_token)   # (B, S, K)
    if cfg.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, idx


def moe_route(cfg: ModelConfig, router: jnp.ndarray, xc: jnp.ndarray):
    """Top-k routing + per-row capacity bookkeeping.

    The single owner of the routing math: both the GSPMD dense path
    (:func:`moe`) and the expert-parallel dispatch path
    (``models/moe_ep.py``) call it, which is what makes the two paths
    token-for-token equivalent (same slots, same drops).

    Returns ``(weights (B,S,K), idx (B,S,K), keep (B,S,K) bool,
    dst (B,S,K) flat slot with ``e*cap`` as the overflow bin, cap)``.
    """
    b, s, _ = xc.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    weights, idx = moe_topk(cfg, router, xc)
    cap = max(1, int(s * k / e * cfg.capacity_factor))
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)      # (B, S, K, E)
    flat_choice = onehot.reshape(b, s * k, e)
    pos_in_e = jnp.cumsum(flat_choice, axis=1) - flat_choice  # (B, S*K, E)
    slot = jnp.take_along_axis(
        pos_in_e.reshape(b, s, k, e), idx[..., None], axis=-1
    )[..., 0]                                              # (B, S, K)
    keep = (slot < cap)
    dst = jnp.where(keep, idx * cap + slot, e * cap)       # overflow bin
    return weights, idx, keep, dst, cap


def moe_dispatch(xc: jnp.ndarray, dst: jnp.ndarray, keep: jnp.ndarray,
                 e: int, cap: int) -> jnp.ndarray:
    """Scatter tokens into the per-expert capacity buffer.

    ``xc``: (B, S, D); ``dst``/``keep`` from :func:`moe_route`.  Returns the
    (B, E, cap, D) buffer — dropped (over-capacity) tokens land in the
    overflow bin and are sliced away.
    """
    b, s, d = xc.shape
    k = dst.shape[-1]
    xin = jnp.zeros((b, e * cap + 1, d), xc.dtype)
    src = jnp.broadcast_to(xc[:, :, None, :], (b, s, k, d)).reshape(b, s * k, d)
    xin = xin.at[jnp.arange(b)[:, None], dst.reshape(b, s * k)].add(
        src * keep.reshape(b, s * k, 1))
    return xin[:, : e * cap].reshape(b, e, cap, d)


def moe_combine(ye: jnp.ndarray, dst: jnp.ndarray, keep: jnp.ndarray,
                weights: jnp.ndarray) -> jnp.ndarray:
    """Gather expert outputs back to token order and mix by router weights.

    ``ye``: (B, E, cap, D) expert outputs; dropped tokens contribute zero
    (residual fallthrough happens at the block level).  Returns (B, S, D).
    """
    b, e, cap, d = ye.shape
    s, k = dst.shape[1], dst.shape[2]
    ye = ye.reshape(b, e * cap, d)
    ye = jnp.concatenate([ye, jnp.zeros((b, 1, d), ye.dtype)], axis=1)
    gathered = jnp.take_along_axis(
        ye, dst.reshape(b, s * k, 1), axis=1
    ).reshape(b, s, k, d)
    return (gathered * (weights * keep).astype(ye.dtype)[..., None]).sum(axis=2)


def moe(cfg: ModelConfig, params: Params, x: jnp.ndarray,
        dense_combine: bool = False) -> jnp.ndarray:
    """x: (B, S, D).  Routing/capacity are computed *per batch row*, so the
    whole layer partitions cleanly over the data axis (capacity per row ==
    per-device capacity with row-aligned sharding).  Dropped tokens (over
    capacity) fall through on the residual path, as in standard top-k MoE.

    ``dense_combine=True`` computes every held expert on every token and
    mixes by router weights — used for decode, where S is 1 and the layer
    is bound by reading the expert *weights* anyway, so the extra FLOPs
    are free and the gather/scatter (and its collectives) disappear.

    ``capacity_factor=None`` routes dropless: every (token, held expert)
    pair is computed, by the grouped matmul of :func:`moe_dropless`.
    Routing is over all ``n_experts`` router outputs; the layer computes
    the part of the result that its held experts
    ``[expert_offset, expert_offset + n_local_experts)`` give.
    """
    b, s, d = x.shape
    e = cfg.n_experts
    cd = _cdtype(cfg)
    xc = x.astype(cd)

    if dense_combine:
        with jax.named_scope("moe.route"):
            weights, idx = moe_topk(cfg, params["router"], xc)
            combine = jnp.zeros((b, s, e), jnp.float32).at[
                jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
                idx].add(weights)
            held = lax.slice_in_dim(combine, cfg.expert_offset,
                                    cfg.expert_offset + cfg.n_local_experts,
                                    axis=2)
        with jax.named_scope("moe.experts"):
            dense = _expert_ffn(cfg, params, jnp.broadcast_to(
                xc[:, None], (b, cfg.n_local_experts, s, d)))
        with jax.named_scope("moe.combine"):
            y = jnp.einsum("besd,bse->bsd", dense, held.astype(cd))
    elif cfg.capacity_factor is None:
        with jax.named_scope("moe.route"):
            weights, idx = moe_topk(cfg, params["router"], xc)
        y = moe_dropless(cfg, params, xc, weights, idx)
    else:
        assert cfg.n_local_experts == e, "capacity routing holds every expert"
        weights, _, keep, dst, cap = moe_route(cfg, params["router"], xc)
        xe = moe_dispatch(xc, dst, keep, e, cap)
        ye = _expert_ffn(cfg, params, xe)
        y = moe_combine(ye, dst, keep, weights)

    if cfg.n_shared_experts:
        y = y + mlp(cfg, params["shared"], xc)
    return y.astype(x.dtype)


def _gmm(cfg: ModelConfig, lhs: jnp.ndarray, rhs: jnp.ndarray,
         sizes: jnp.ndarray) -> jnp.ndarray:
    """The grouped matmul of the resolved kernel path: the Pallas
    ``moe_gmm`` under ``pallas``, ``lax.ragged_dot`` otherwise."""
    from repro.kernels.grouped_matmul import gmm_ref, moe_gmm

    if resolve_attn_impl(cfg) == "pallas":
        return moe_gmm(lhs, rhs, sizes)
    return gmm_ref(lhs, rhs, sizes)


def moe_dropless(cfg: ModelConfig, params: Params, xc: jnp.ndarray,
                 weights: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """The held experts' part of a dropless MoE layer, float32-combined.

    The (token, expert) pairs routed to a held expert are sorted by
    expert into row groups, each group padded to a multiple of the
    kernel's ``TILE_M`` rows; the gate, up and down products run as three
    grouped matmuls over them, and each token gathers back its pairs'
    rows and mixes them by router weight.  Pairs routed to experts held
    elsewhere contribute nothing.  Shapes are static: the row extent is
    the worst case, ``T·min(K, H)`` rows plus a tile of padding a group,
    and the kernel computes only the tiles that hold rows."""
    from repro.kernels.grouped_matmul import TILE_M

    b, s, d = xc.shape
    t, k = b * s, idx.shape[-1]
    h = cfg.n_local_experts
    tm = TILE_M
    m_cap = -(-(t * min(k, h) + h * (tm - 1)) // tm) * tm
    with jax.named_scope("moe.route"):
        local = idx.reshape(t * k) - cfg.expert_offset
        held = (local >= 0) & (local < h)
        group = jnp.where(held, local, h)           # h: held elsewhere
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((h + 1,), jnp.int32).at[group].add(1)
        start = jnp.cumsum(sizes) - sizes           # unpadded group starts
        padded = (sizes[:h] + tm - 1) // tm * tm
        pstart = jnp.concatenate([jnp.cumsum(padded) - padded,
                                  jnp.full((1,), m_cap, jnp.int32)])
        g_sorted = group[order]
        row_sorted = jnp.where(
            g_sorted < h,
            pstart[g_sorted] + jnp.arange(t * k) - start[g_sorted], m_cap)
        row = jnp.zeros((t * k,), jnp.int32).at[order].set(row_sorted)
        token_of_row = jnp.full((m_cap + 1,), t, jnp.int32).at[row].set(
            jnp.arange(t * k, dtype=jnp.int32) // k)[:m_cap]
        x_rows = jnp.concatenate([xc.reshape(t, d),
                                  jnp.zeros((1, d), xc.dtype)])[token_of_row]
    with jax.named_scope("moe.experts"):
        cd = _cdtype(cfg)
        up = _gmm(cfg, x_rows, params["w_up"].astype(cd), padded)
        if cfg.gated_mlp:
            up = _act(cfg.activation,
                      _gmm(cfg, x_rows, params["w_gate"].astype(cd),
                           padded)) * up
        else:
            up = _act(cfg.activation, up)
        out = _gmm(cfg, up, params["w_down"].astype(cd), padded)
    with jax.named_scope("moe.combine"):
        got = out[jnp.minimum(row, m_cap - 1)].reshape(t, k, d)
        got = jnp.where(held.reshape(t, k, 1), got.astype(jnp.float32), 0.0)
        y = (got * weights.reshape(t, k, 1)).sum(axis=1)
    return y.reshape(b, s, d)


def moe_aux_loss(cfg: ModelConfig, x: jnp.ndarray, params: Params) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style): E[f_e · p_e] · E."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = lax.top_k(probs, cfg.experts_per_token)
    hard = jax.nn.one_hot(idx, cfg.n_experts).sum(axis=2)  # (B, S, E)
    f = hard.mean(axis=(0, 1))
    p = probs.mean(axis=(0, 1))
    return cfg.n_experts * jnp.sum(f * p)


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) block
# ---------------------------------------------------------------------------


def init_mamba2(cfg: ModelConfig, key) -> Params:
    dt = _dtype(cfg)
    d_in = cfg.ssm_heads * cfg.ssm_head_dim
    conv_ch = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
    ks = jax.random.split(key, 5)
    proj_out = 2 * d_in + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads
    depth_scale = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    return {
        "in_proj": _init(ks[0], (cfg.d_model, proj_out), dt),
        "conv_w": _init(ks[1], (cfg.ssm_conv, conv_ch), dt, 0.1),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "dt_bias": jnp.zeros((cfg.ssm_heads,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, cfg.ssm_heads, dtype=jnp.float32)),
        "d_skip": jnp.ones((cfg.ssm_heads,), jnp.float32),
        "gate_norm": {"scale": jnp.ones((d_in,), dt)},
        "out_proj": _init(ks[2], (d_in, cfg.d_model), dt, depth_scale),
    }


def _causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                   pad: bool = True) -> jnp.ndarray:
    """Depthwise causal conv over seq.  x: (B, S, C); w: (K, C).

    ``pad=False`` skips the leading zero-pad: the caller has already
    prepended the (K−1) preceding raw rows (the chunked-prefill conv
    resume), so VALID alignment alone yields the causal outputs — the same
    conv the padded call runs, since concatenated zeros and pad zeros are
    the same input tensor."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))) if pad else x
    out = lax.conv_general_dilated(
        xp, w[:, None, :],          # (K, 1, C) HIO with feature groups
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=x.shape[-1],
    )
    return out + b


def ssd_jnp(x, dtv, a, bmat, cmat, d_skip, chunk: int, init_state=None):
    """Chunked SSD in pure jnp (same math as the Pallas kernel): scan over
    chunks carrying the (H, N, P) state; intra-chunk work is batched matmuls.

    x: (B, S, H, P); dtv: (B, S, H); a: (H,); bmat/cmat: (B, S, G, N).
    Returns (y, final_state (B, H, N, P) fp32).

    ``init_state`` resumes the chunk walk from a carried (B, H, N, P) fp32
    state (the streamed-prefill hand-off) instead of zeros — bit-identical
    to one bulk call over the concatenated sequence whenever the resume
    point is a multiple of ``chunk`` (the walk visits the same blocks).
    """
    bsz, s, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    hpg = h // g
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtv = jnp.pad(dtv, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = x.shape[1] // chunk

    def reshape_c(t):
        return jnp.moveaxis(
            t.reshape((bsz, nc, chunk) + t.shape[2:]), 1, 0
        )  # (nc, B, L, ...)

    xs, dts, bs, cs = map(reshape_c, (x, dtv, bmat, cmat))

    def step(state, inp):
        xc_, dt_, b_, c_ = inp                     # (B,L,H,P),(B,L,H),(B,L,G,N)
        xf = xc_.astype(jnp.float32)
        dtf = dt_.astype(jnp.float32)
        alog = dtf * a[None, None, :]              # (B, L, H)
        cum = jnp.cumsum(alog, axis=1)
        total = cum[:, -1]                         # (B, H)
        bh = jnp.repeat(b_, hpg, axis=2).astype(jnp.float32)   # (B,L,H,N)
        ch = jnp.repeat(c_, hpg, axis=2).astype(jnp.float32)
        seg = cum[:, :, None, :] - cum[:, None, :, :]          # (B,L,L,H)
        ii = jnp.arange(chunk)
        causal = ii[:, None] >= ii[None, :]
        seg = jnp.where(causal[None, :, :, None], seg, -1e30)
        scores = jnp.einsum("blhn,bmhn->blmh", ch, bh)
        w = scores * jnp.exp(seg) * dtf[:, None, :, :]
        y = jnp.einsum("blmh,bmhp->blhp", w, xf)
        y += jnp.exp(cum)[..., None] * jnp.einsum("blhn,bhnp->blhp", ch, state)
        decay_end = jnp.exp(total[:, None] - cum) * dtf        # (B,L,H)
        state = jnp.exp(total)[..., None, None] * state + jnp.einsum(
            "blhn,blhp->bhnp", bh * decay_end[..., None], xf)
        return state, y

    state0 = (jnp.zeros((bsz, h, n, p), jnp.float32) if init_state is None
              else init_state.astype(jnp.float32))
    final, ys = lax.scan(step, state0, (xs, dts, bs, cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, nc * chunk, h, p)[:, :s]
    y = y + d_skip[None, None, :, None] * x[:, :s].astype(jnp.float32)
    return y, final


def mamba2_block(cfg: ModelConfig, params: Params, x: jnp.ndarray,
                 return_state: bool = False, init_state=None,
                 conv_state=None):
    """x: (B, S, D) -> (B, S, D).  Mamba-2 block: in_proj → causal conv →
    SSD (Pallas kernel on TPU, chunked jnp elsewhere) → gated RMSNorm →
    out_proj.  ``return_state`` also returns the decode cache contents:
    (final ssm state (B,H,N,P) fp32, conv tail (B, conv−1, C) raw pre-conv).

    ``init_state`` / ``conv_state`` resume a *mid-sequence* forward (the
    streamed-prefill chunk carry): ``init_state`` seeds the SSD chunk walk
    and ``conv_state`` supplies the (conv−1) raw pre-conv rows preceding
    this slice, which are prepended so the depthwise conv runs VALID over
    the extended stream — the exact rows the bulk conv would see.  With
    zero carries this is bitwise the plain call (prepended zeros ≡ the
    causal zero-pad), so chunk 0 needs no special case."""
    b, s, _ = x.shape
    h, p, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    d_in = h * p
    cd = _cdtype(cfg)
    xc = x.astype(cd)

    zxbcdt = xc @ params["in_proj"].astype(cd)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in: 2 * d_in + 2 * g * n]
    dt_raw = zxbcdt[..., 2 * d_in + 2 * g * n:]

    tail_len = cfg.ssm_conv - 1
    if conv_state is not None:
        # resume: the raw rows preceding this slice, carried by the caller
        assert conv_state.shape[1] == tail_len, conv_state.shape
        ext = jnp.concatenate([conv_state.astype(cd), xbc], axis=1)
        if return_state:
            conv_tail = ext[:, ext.shape[1] - tail_len:, :]
        xbc = _causal_conv1d(ext, params["conv_w"].astype(cd),
                             params["conv_b"].astype(cd), pad=False)
    else:
        if return_state:
            # decode resumes the depthwise conv from the last (conv−1) raw
            # inputs
            pad = max(0, tail_len - s)
            tail_src = (jnp.pad(xbc, ((0, 0), (pad, 0), (0, 0)))
                        if pad else xbc)
            conv_tail = tail_src[:, -tail_len:, :]
        xbc = _causal_conv1d(xbc, params["conv_w"].astype(cd),
                             params["conv_b"].astype(cd))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(b, s, h, p)
    bmat = xbc[..., d_in: d_in + g * n].reshape(b, s, g, n)
    cmat = xbc[..., d_in + g * n:].reshape(b, s, g, n)
    dtv = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"][None, None, :]
    )
    a = -jnp.exp(params["a_log"])

    impl = resolve_attn_impl(cfg)
    if impl == "pallas":
        from repro.kernels.ssd import ssd as ssd_kernel, ssd_chunk_fed

        n_seg = int(cfg.ssm_stream_segments or 0)
        if n_seg > 1 and s > cfg.ssm_chunk:
            # chunk-fed scan: feed the kernel segment-by-segment with the
            # state carried across segments.  Segment cuts land on chunk
            # boundaries (tail rides the last segment), so the walk is
            # bit-identical to the bulk call.
            from repro.core.pipeline import chunk_slices
            full = s // cfg.ssm_chunk
            cuts = [(lo * cfg.ssm_chunk, hi * cfg.ssm_chunk)
                    for lo, hi in chunk_slices(full, min(n_seg, full))]
            cuts[-1] = (cuts[-1][0], s)

            def fetch(k):
                lo, hi = cuts[k]
                return (xs[:, lo:hi], dtv[:, lo:hi],
                        bmat[:, lo:hi], cmat[:, lo:hi])

            y, state = ssd_chunk_fed(fetch, len(cuts), a, params["d_skip"],
                                     chunk=cfg.ssm_chunk,
                                     init_state=init_state)
        else:
            y, state = ssd_kernel(xs, dtv, a, bmat, cmat, params["d_skip"],
                                  chunk=cfg.ssm_chunk, init_state=init_state)
        y = y.astype(jnp.float32)
    else:
        y, state = ssd_jnp(xs, dtv, a, bmat, cmat, params["d_skip"],
                           chunk=cfg.ssm_chunk, init_state=init_state)

    y = y.reshape(b, s, d_in).astype(cd)
    y = rms_norm(params["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = (y @ params["out_proj"].astype(cd)).astype(x.dtype)
    if return_state:
        return out, (state, conv_tail)
    return out
