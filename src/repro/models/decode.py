"""Serving path: per-family caches + single-token decode step.

``decode_*`` shapes in the dry-run lower exactly this ``decode_step`` (one
new token against a populated cache), never ``train_step``.

Cache design notes (these drive the decode-shape roofline memory term):

* Positions are **per slot**: ``cache["pos"]`` is ``(B,)`` and
  ``slot_pos`` is ``(B, S_buf)``, so every batch row of the cache advances
  independently — the continuous-batching server admits a freshly
  prefilled request into one row while the other rows keep decoding at
  their own positions (``runtime/server.py``).
* GQA: ring-buffer K/V — ``S_buf = min(max_seq, window)``; for h2o-danube's
  4096-token sliding window the long_500k cache is 4096 slots, not 500k
  (the reason the arch runs that shape at all).  A per-row ``slot_pos``
  array maps buffer slots to absolute positions; masking validates
  ``pos - window < slot_pos <= pos``.  The decode step carries the
  stacked (L, B, Hkv, S_buf, hd) ring through its layer loop and, per
  layer, writes each row's one new K/V vector in place before reading the
  layer's slab for attention: with the cache donated, the step moves the
  ring once (the attention's read) and copies none of it.
* MLA (minicpm3, deepseek-v2-lite): caches the latent (256 / 512-d) + the
  shared rope key (32 / 64-d) instead of per-head K/V, and uses the
  *absorbed* formulation (W_uk folded into the query, W_uv into the
  output) so per-token work is O(S_buf · r).  The stacked latent rings
  ride the layer loops' carry like the GQA ring, one row written in
  place per layer; a MoE stack's leading dense layers run first, with the
  running layer index.
* SSD: O(1) state — (H, N, P) fp32 per layer + a (conv−1)-deep conv ring.
* hybrid: SSM states for all 81 layers + one K/V cache per *application*
  of the shared attention block (weights are shared; caches are not).
* encdec: decoder self-attention ring + precomputed cross K/V per layer.

**Paged KV block pool** (PR 6): :func:`init_paged_cache` replaces the
per-row contiguous ring with fixed-size blocks drawn from one shared pool
(``kp``/``vp``: (L, N_blocks, Hkv, blk, hd)) plus a per-slot block table
(``block_ids`` (B, S_buf/blk)).  ``block_size`` must divide
``kv_buf_len`` so ring slot ``j`` lives in block ``j // blk`` at offset
``j % blk`` — the block-table gather then reconstructs *exactly* the
contiguous layout, the attention math is byte-for-byte the contiguous
recipe, and only the new row is scattered back — which is what makes
paged decode bit-identical to the contiguous path (asserted by
tests/test_serving.py across block sizes, ring wraparound, and
shared-prefix aliasing).  Blocks ``[0, batch)`` are per-row *parking*
blocks: rows whose slot is idle keep writing into their own parking
block, so a retired row can never clobber a block the allocator
(``runtime/server.BlockPool``) has handed to someone else.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.model import _lm_logits

Params = Dict[str, Any]
Cache = Dict[str, Any]


def _cd(cfg):
    return jnp.dtype(cfg.compute_dtype)


#: decoder self-attention ring cap for encdec archs (whisper-style)
ENCDEC_DECODER_CAP = 4096


def kv_buf_len(cfg: ModelConfig, max_seq: int) -> int:
    """Ring-buffer extent of the K/V cache for ``max_seq`` positions.

    The one owner of the sizing rule — ``init_cache``, both prefill paths
    (``models/prefill.py``), and the step builders all call it: the SWA
    window caps the buffer (h2o-danube keeps 4096 slots at 500k context),
    and encdec decoders cap at :data:`ENCDEC_DECODER_CAP`.
    """
    if cfg.family == "encdec":
        return min(max_seq, ENCDEC_DECODER_CAP)
    return min(max_seq, cfg.window) if cfg.window else max_seq


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               enc_out: Optional[jnp.ndarray] = None,
               params: Optional[Params] = None) -> Cache:
    dt = jnp.dtype(cfg.param_dtype)
    hd = cfg.resolved_head_dim if cfg.n_heads else 0
    sb = kv_buf_len(cfg, max_seq)
    cache: Cache = {"pos": jnp.zeros((batch,), jnp.int32)}

    if cfg.family in ("dense", "vlm", "moe") and cfg.attn_type != "mla":
        cache["k"] = jnp.zeros((cfg.n_layers, batch, cfg.n_kv_heads, sb, hd), dt)
        cache["v"] = jnp.zeros((cfg.n_layers, batch, cfg.n_kv_heads, sb, hd), dt)
        cache["slot_pos"] = jnp.full((batch, sb), -1, jnp.int32)
    elif cfg.attn_type == "mla":
        cache["ckv"] = jnp.zeros((cfg.n_layers, batch, sb, cfg.kv_lora_rank), dt)
        cache["krope"] = jnp.zeros((cfg.n_layers, batch, sb, cfg.qk_rope_dim), dt)
        cache["slot_pos"] = jnp.full((batch, sb), -1, jnp.int32)
    elif cfg.family == "ssm":
        cache.update(_ssm_cache(cfg, cfg.n_layers, batch, dt))
    elif cfg.family == "hybrid":
        cache.update(_ssm_cache(cfg, cfg.n_layers, batch, dt))
        n_apps = cfg.n_layers // cfg.hybrid_period
        cache["attn_k"] = jnp.zeros((n_apps, batch, cfg.n_kv_heads, sb, hd), dt)
        cache["attn_v"] = jnp.zeros((n_apps, batch, cfg.n_kv_heads, sb, hd), dt)
        cache["slot_pos"] = jnp.full((batch, sb), -1, jnp.int32)
    elif cfg.family == "encdec":
        sdec = kv_buf_len(cfg, max_seq)
        cache["k"] = jnp.zeros((cfg.n_layers, batch, cfg.n_kv_heads, sdec, hd), dt)
        cache["v"] = jnp.zeros((cfg.n_layers, batch, cfg.n_kv_heads, sdec, hd), dt)
        cache["slot_pos"] = jnp.full((batch, sdec), -1, jnp.int32)
        if enc_out is not None:
            assert params is not None
            def xkv(lp):
                k, v, _ = L.cross_kv(cfg, lp["xattn"], enc_out)
                return k.astype(dt), v.astype(dt)
            ks, vs = jax.vmap(xkv)(params["dec_layers"])
            cache["cross_k"], cache["cross_v"] = ks, vs
        else:
            cache["cross_k"] = jnp.zeros(
                (cfg.n_layers, batch, cfg.n_kv_heads, cfg.encoder_seq, hd), dt)
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    return cache


def _ssm_cache(cfg: ModelConfig, n_layers: int, batch: int, dt) -> Cache:
    d_in = cfg.ssm_heads * cfg.ssm_head_dim
    conv_ch = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
    return {
        "ssm_state": jnp.zeros(
            (n_layers, batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
            jnp.float32),
        "conv_state": jnp.zeros((n_layers, batch, cfg.ssm_conv - 1, conv_ch), dt),
    }


def supports_paged(cfg: ModelConfig) -> bool:
    """Whether the arch can decode against the paged KV block pool.

    Requires the GQA ring-buffer cache (dense/vlm/moe non-MLA) — the
    families whose ``k``/``v`` leaves the block table indirects.  MLA
    latents, SSM state and the encdec cross-cache stay contiguous.  The
    rule itself lives in the jax-free capability table
    (``configs.base.serving_features``) so docs and tools can query it.
    """
    from repro.configs.base import serving_features

    return serving_features(cfg)["paged"]


def paged_slot_blocks(cfg: ModelConfig, max_seq: int, block_size: int) -> int:
    """Blocks per slot: ``kv_buf_len / block_size``.

    ``block_size`` must divide the ring extent — that is the invariant
    that keeps ring slot ``j`` at block ``j // blk`` offset ``j % blk``,
    i.e. the gathered view *is* the contiguous layout (bit-identity).
    """
    sb = kv_buf_len(cfg, max_seq)
    if sb % block_size:
        raise ValueError(
            f"block_size {block_size} must divide kv_buf_len {sb}")
    return sb // block_size


def init_paged_cache(cfg: ModelConfig, batch: int, max_seq: int,
                     block_size: int, n_blocks: int) -> Cache:
    """A paged decode cache: shared block pool + per-slot block tables.

    Layout (vs the contiguous ``init_cache``): ``k``/``v``
    (L, B, Hkv, S_buf, hd) become ``kp``/``vp`` (L, n_blocks, Hkv,
    block_size, hd), and ``block_ids`` (B, S_buf/block_size) maps each
    slot's logical block to a pool block.  Blocks ``[0, batch)`` are the
    per-row parking blocks; every row's table starts parked on its own
    (``block_ids[b, :] = b``), so idle rows write garbage only into
    their private parking block.  ``pos``/``slot_pos`` bookkeeping is
    unchanged from the contiguous contract.
    """
    assert supports_paged(cfg), cfg.name
    dt = jnp.dtype(cfg.param_dtype)
    hd = cfg.resolved_head_dim
    sb = kv_buf_len(cfg, max_seq)
    npb = paged_slot_blocks(cfg, max_seq, block_size)
    if n_blocks < batch:
        raise ValueError(
            f"n_blocks {n_blocks} < batch {batch}: every row needs a "
            f"parking block")
    shape = (cfg.n_layers, n_blocks, cfg.n_kv_heads, block_size, hd)
    return {
        "pos": jnp.zeros((batch,), jnp.int32),
        "slot_pos": jnp.full((batch, sb), -1, jnp.int32),
        "kp": jnp.zeros(shape, dt),
        "vp": jnp.zeros(shape, dt),
        "block_ids": jnp.broadcast_to(
            jnp.arange(batch, dtype=jnp.int32)[:, None], (batch, npb)),
    }


def gather_blocks(pool: jnp.ndarray, block_ids: jnp.ndarray) -> jnp.ndarray:
    """Block-table gather: pool (N, Hkv, blk, hd) + table (B, npb) →
    the contiguous-layout view (B, Hkv, npb·blk, hd).  A pure gather —
    the bits are exactly the contiguous cache's, so everything computed
    from the view is bit-identical to the contiguous path."""
    g = jnp.take(pool, block_ids, axis=0)          # (B, npb, Hkv, blk, hd)
    b, npb, hkv, blk, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, npb * blk, hd)


def scatter_block_rows(pool: jnp.ndarray, block_ids: jnp.ndarray,
                       new: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """Write each row's new K/V vector (B, Hkv, hd) into its pool block.

    Ring slot ``slot[b]`` lives in block ``block_ids[b, slot // blk]`` at
    offset ``slot % blk`` — the one-row scatter that replaces the
    contiguous path's ``_row_update``.  The allocator guarantees distinct
    rows never share a *tail* block (shared prefix blocks are read-only
    by the admission rule), so the scatter has no write aliasing."""
    blk = pool.shape[2]
    bid = jnp.take_along_axis(block_ids, (slot // blk)[:, None], axis=1)[:, 0]
    return pool.at[bid, :, slot % blk, :].set(new.astype(pool.dtype))


def cache_bytes(cfg: ModelConfig, batch: int, max_seq: int) -> int:
    """Analytic cache footprint (roofline memory term for decode shapes)."""
    c = init_cache(cfg, 1, 8)  # layout probe, tiny
    del c
    leaves = jax.eval_shape(lambda: init_cache(cfg, batch, max_seq))
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(leaves))


# ---------------------------------------------------------------------------
# per-layer decode primitives
# ---------------------------------------------------------------------------


def _valid_slots(slot_pos, pos, window):
    """Per-row key validity: ``slot_pos`` (B, S_buf) against ``pos`` (B,)."""
    valid = slot_pos >= 0
    valid &= slot_pos <= pos[:, None]
    if window is not None:
        valid &= slot_pos > (pos - window)[:, None]
    return valid


def _row_update(buf, new, slot):
    """Write ``new`` (B, ..., 1, d) into ``buf`` (B, ..., S_buf, d) at the
    per-row ring slot ``slot`` (B,) — the vmapped dynamic-update the shared
    scalar position used to do in one call."""
    def one(b, n, s):
        start = (0,) * (b.ndim - 2) + (s, 0)
        return lax.dynamic_update_slice(b, n, start)

    return jax.vmap(one)(buf, new.astype(buf.dtype), slot)


def _ring_write(ring, new, layer, slot):
    """Write each row's new vector ``new`` (B, ..., d) into the stacked
    ring (L, B, ..., S_buf, d) at ``[layer, b, ..., slot[b], :]``, in
    place: the GQA ring (L, B, Hkv, S_buf, hd) takes (B, Hkv, hd), the
    latent rings (L, B, S_buf, r) take (B, r).

    One dynamic-update-slice per row: a scatter would make the TPU
    compiler lay the whole ring out again with (Hkv, hd) minor (padded
    3.2x, copied in and out of the loop), while an update slice writes
    into the S-minor layout the ring keeps."""
    new = new.astype(ring.dtype)
    seq_axis = ring.ndim - 2
    mid = (0,) * (ring.ndim - 4)
    for r in range(new.shape[0]):
        ring = lax.dynamic_update_slice(
            ring, jnp.expand_dims(new[r], (0, 1, seq_axis)),
            (layer, r) + mid + (slot[r], 0))
    return ring


def _masked_softmax_attend(scores, vcache, slot_pos, pos, window):
    """scores: (B, Hkv, G, S_buf) fp32; vcache: (B, Hkv, S_buf, hd);
    ``slot_pos`` (B, S_buf) / ``pos`` (B,) are per batch row."""
    valid = _valid_slots(slot_pos, pos, window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    m = scores.max(-1, keepdims=True)
    p = jnp.where(scores <= -1e29, 0.0, jnp.exp(scores - m))
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    p = p / denom
    return jnp.einsum("bkgs,bksd->bkgd", p, vcache.astype(jnp.float32))


def _decode_qkv(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                pos: jnp.ndarray, rope: bool = True):
    """The new token's projections: q (B, Hq, hd), k/v (B, Hkv, hd) in
    the compute dtype, rotated to the per-row ``pos`` (B,)."""
    b, _ = x.shape
    hd = cfg.resolved_head_dim
    hkv, hq = cfg.n_kv_heads, cfg.n_heads
    cd = _cd(cfg)
    xc = x.astype(cd)

    q = (xc @ p["wq"].astype(cd)).reshape(b, hq, hd)
    k = (xc @ p["wk"].astype(cd)).reshape(b, hkv, hd)
    v = (xc @ p["wv"].astype(cd)).reshape(b, hkv, hd)
    if rope:
        posv = pos[:, None, None]
        q = L.apply_rope(q[:, :, None, :], posv, cfg.rope_theta)[:, :, 0]
        k = L.apply_rope(k[:, :, None, :], posv, cfg.rope_theta)[:, :, 0]
    return q, k, v


def _decode_attend(cfg: ModelConfig, p: Params, q: jnp.ndarray,
                   kc: jnp.ndarray, vc: jnp.ndarray,
                   slot_pos_new: jnp.ndarray, pos: jnp.ndarray,
                   window: Optional[int]):
    """q (B, Hq, hd) against one layer's ring kc/vc (B, Hkv, S_buf, hd),
    which already holds the new row; returns the output projection (B, D)
    in the compute dtype."""
    b = q.shape[0]
    hd = cfg.resolved_head_dim
    hkv, hq = cfg.n_kv_heads, cfg.n_heads
    g = hq // hkv
    cd = _cd(cfg)
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32) * hd ** -0.5
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, kc.astype(jnp.float32))
    out = _masked_softmax_attend(scores, vc, slot_pos_new, pos, window)
    out = out.reshape(b, hq * hd).astype(cd)
    return out @ p["wo"].astype(cd)


def attention_decode(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                     kc: jnp.ndarray, vc: jnp.ndarray,
                     slot_pos_new: jnp.ndarray, pos: jnp.ndarray,
                     rope: bool = True, window: Optional[int] = None):
    """x: (B, D) single token; ``pos`` (B,) per-row; kc/vc one layer's
    ring (B, Hkv, S_buf, hd).  Returns (out (B, D), kc, vc)."""
    q, k, v = _decode_qkv(cfg, p, x, pos, rope)
    slot = pos % kc.shape[2]
    kc = _row_update(kc, k[:, :, None, :], slot)
    vc = _row_update(vc, v[:, :, None, :], slot)
    out = _decode_attend(cfg, p, q, kc, vc, slot_pos_new, pos, window)
    return out.astype(x.dtype), kc, vc


def cross_attention_decode(cfg, p, x, kc, vc, n_valid: int):
    """Cross-attention against static (precomputed) encoder K/V."""
    b, _ = x.shape
    hd = cfg.resolved_head_dim
    hkv, hq = cfg.n_kv_heads, cfg.n_heads
    g = hq // hkv
    cd = _cd(cfg)
    q = (x.astype(cd) @ p["wq"].astype(cd)).reshape(b, hkv, g, hd)
    scores = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32) * hd ** -0.5,
                        kc.astype(jnp.float32))
    m = scores.max(-1, keepdims=True)
    pr = jnp.exp(scores - m)
    pr = pr / jnp.maximum(pr.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgs,bksd->bkgd", pr, vc.astype(jnp.float32))
    out = out.reshape(b, hq * hd).astype(cd)
    return (out @ p["wo"].astype(cd)).astype(x.dtype)


def _mla_decode_q(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                  pos: jnp.ndarray):
    """The new token's absorbed MLA query and latent cache row.  x: (B, D);
    ``pos`` (B,) per row.  Returns ``q_eff`` (B, H, r) with W_uk folded
    in, ``q_rope`` (B, H, dr), and the rows ``c_new`` (B, r) and
    ``kr_new`` (B, dr) of the latent and rope-key rings."""
    h, dn = cfg.n_heads, cfg.qk_nope_dim
    r = cfg.kv_lora_rank
    cd = _cd(cfg)
    xc = x.astype(cd)
    q = L.mla_query(cfg, p, xc)                           # (B, H, dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.mla_rope(cfg, q_rope[:, :, None, :], pos[:, None, None])[:, :, 0]
    with jax.named_scope("mla.absorb"):
        w_uk = p["w_uk"].astype(cd).reshape(r, h, dn)
        q_eff = jnp.einsum("bhd,rhd->bhr", q_nope, w_uk)  # absorb W_uk
    dkv = xc @ p["w_dkv"].astype(cd)
    c_new = L.rms_norm(p["kv_norm"], dkv[:, :r], cfg.norm_eps)
    kr_new = L.mla_rope(cfg, dkv[:, None, None, r:],
                        pos[:, None, None])[:, 0, 0]
    return q_eff, q_rope, c_new, kr_new


def _mla_decode_attend(cfg: ModelConfig, p: Params, q_eff: jnp.ndarray,
                       q_rope: jnp.ndarray, ckv: jnp.ndarray,
                       krope: jnp.ndarray, slot_pos_new: jnp.ndarray,
                       pos: jnp.ndarray):
    """Absorbed MLA attention of the new token over one layer's rings
    ``ckv`` (B, S_buf, r) / ``krope`` (B, S_buf, dr), which already hold
    its row; returns the output projection (B, D) in the compute dtype."""
    b = q_eff.shape[0]
    h, dv = cfg.n_heads, cfg.v_head_dim
    r = cfg.kv_lora_rank
    cd = _cd(cfg)
    with jax.named_scope("mla.attend"):
        scores = (jnp.einsum("bhr,bsr->bhs", q_eff.astype(jnp.float32),
                             ckv.astype(jnp.float32))
                  + jnp.einsum("bhd,bsd->bhs", q_rope.astype(jnp.float32),
                               krope.astype(jnp.float32))
                  ) * L.mla_softmax_scale(cfg)
        valid = _valid_slots(slot_pos_new, pos, None)
        scores = jnp.where(valid[:, None, :], scores, -1e30)
        m = scores.max(-1, keepdims=True)
        pr = jnp.where(scores <= -1e29, 0.0, jnp.exp(scores - m))
        pr = pr / jnp.maximum(pr.sum(-1, keepdims=True), 1e-30)
        out_lat = jnp.einsum("bhs,bsr->bhr", pr, ckv.astype(jnp.float32))
    with jax.named_scope("mla.absorb"):
        w_uv = p["w_uv"].astype(cd).reshape(r, h, dv)
        out = jnp.einsum("bhr,rhd->bhd", out_lat.astype(cd), w_uv)  # W_uv
    out = out.reshape(b, h * dv)
    return out @ p["wo"].astype(cd)


def mamba2_decode(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                  ssm_state: jnp.ndarray, conv_state: jnp.ndarray):
    """Single-token Mamba-2 step.  x: (B, D); ssm_state: (B, H, N, P) fp32;
    conv_state: (B, conv-1, conv_ch)."""
    from repro.kernels.ssd.ref import ssd_decode_step

    b, _ = x.shape
    h, pdim, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    d_in = h * pdim
    cd = _cd(cfg)
    xc = x.astype(cd)

    zxbcdt = xc @ p["in_proj"].astype(cd)
    z = zxbcdt[:, :d_in]
    xbc_new = zxbcdt[:, d_in: 2 * d_in + 2 * g * n]
    dt_raw = zxbcdt[:, 2 * d_in + 2 * g * n:]

    # conv ring: full window = [conv_state ; xbc_new]
    win = jnp.concatenate([conv_state, xbc_new[:, None, :]], axis=1)  # (B,K,C)
    conv_out = jnp.einsum("bkc,kc->bc", win.astype(cd), p["conv_w"].astype(cd))
    conv_out = jax.nn.silu(conv_out + p["conv_b"].astype(cd))
    conv_state = win[:, 1:, :]

    xs = conv_out[:, :d_in].reshape(b, h, pdim)
    bmat = conv_out[:, d_in: d_in + g * n].reshape(b, g, n)
    cmat = conv_out[:, d_in + g * n:].reshape(b, g, n)
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, :])
    a = -jnp.exp(p["a_log"])

    ssm_state, y = ssd_decode_step(ssm_state, xs, dtv, a, bmat, cmat, p["d_skip"])
    y = y.reshape(b, d_in).astype(cd)
    y = L.rms_norm(p["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = (y @ p["out_proj"].astype(cd)).astype(x.dtype)
    return out, ssm_state, conv_state


# ---------------------------------------------------------------------------
# family-level decode step
# ---------------------------------------------------------------------------


def decode_step(cfg: ModelConfig, params: Params, cache: Cache,
                tokens: jnp.ndarray, *,
                moe_runner: Optional[Any] = None) -> Tuple[Cache, jnp.ndarray]:
    """tokens: (B,) int32 — returns (cache', logits (B, V)).

    Every cache row advances at its own ``pos`` (continuous batching).

    ``moe_runner`` (optional) replaces the dense-combine MoE layer with an
    expert-parallel dispatch runner (``models/moe_ep.py`` — the latency-mode
    EP decode: the step's B tokens batched across expert shards through the
    conduit ``all_to_all``).  ``None`` keeps dense-combine, which stays the
    small-batch fallback (weight-bound at decode shapes).
    """
    pos = cache["pos"]
    b = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)  # (B, D)

    if "slot_pos" in cache:
        sb = cache["slot_pos"].shape[1]
        slot_pos_new = cache["slot_pos"].at[jnp.arange(b), pos % sb].set(pos)
    else:
        slot_pos_new = None

    def ffn(normed2, lp):
        """The layer's feed-forward: the MLP, or the MoE of a MoE layer."""
        if "moe" in lp:
            if moe_runner is not None:
                return moe_runner(cfg, lp["moe"], normed2[:, None, :])[:, 0]
            return L.moe(cfg, lp["moe"], normed2[:, None, :],
                         dense_combine=True)[:, 0]
        return L.mlp(cfg, lp["mlp"], normed2)

    # the layer stacks in order: a MoE stack's leading dense layers first
    stacks = [params[k] for k in ("dense_layers", "layers") if k in params]

    if cfg.family in ("dense", "vlm", "moe") and cfg.attn_type != "mla":
        if "kp" in cache:
            # paged: gather the block-table view, run the *identical*
            # contiguous attention, scatter only the new row back
            assert len(stacks) == 1, "the paged pool holds one layer stack"
            bids = cache["block_ids"]
            sb = cache["slot_pos"].shape[1]
            slot = pos % sb

            def body(h, layer):
                lp, kp, vp = layer
                kc = gather_blocks(kp, bids)
                vc = gather_blocks(vp, bids)
                normed = L.apply_norm(cfg, lp["ln1"], h)
                a, kc, vc = attention_decode(
                    cfg, lp["attn"], normed, kc, vc, slot_pos_new, pos,
                    window=cfg.window)
                h = h + a
                f = ffn(L.apply_norm(cfg, lp["ln2"], h), lp)
                rows = jnp.arange(b)
                kp = scatter_block_rows(kp, bids, kc[rows, :, slot, :], slot)
                vp = scatter_block_rows(vp, bids, vc[rows, :, slot, :], slot)
                return h + f, (kp, vp)

            x, (kps, vps) = lax.scan(
                body, x, (params["layers"], cache["kp"], cache["vp"]))
            cache = dict(cache, kp=kps, vp=vps, slot_pos=slot_pos_new,
                         pos=pos + 1)
        else:
            # the stacked (L, B, Hkv, S_buf, hd) ring rides the loop carry:
            # each layer first writes every row's new K/V vector in place
            # at [l, b, :, pos_b % S_buf], then reads its updated slab for
            # attention, so no slab or ring is copied
            slot = pos % cache["k"].shape[3]

            def body(carry, lp):
                h, ks, vs, l = carry
                normed = L.apply_norm(cfg, lp["ln1"], h)
                q, k, v = _decode_qkv(cfg, lp["attn"], normed, pos)
                ks = _ring_write(ks, k, l, slot)
                vs = _ring_write(vs, v, l, slot)
                a = _decode_attend(cfg, lp["attn"], q, ks[l], vs[l],
                                   slot_pos_new, pos, cfg.window)
                h = h + a.astype(normed.dtype)
                f = ffn(L.apply_norm(cfg, lp["ln2"], h), lp)
                return (h + f, ks, vs, l + 1), None

            carry = (x, cache["k"], cache["v"], jnp.int32(0))
            for stack in stacks:
                carry, _ = lax.scan(body, carry, stack)
            x, ks, vs, _ = carry
            cache = dict(cache, k=ks, v=vs, slot_pos=slot_pos_new,
                         pos=pos + 1)

    elif cfg.attn_type == "mla":
        # the stacked latent rings (L, B, S_buf, r) and (L, B, S_buf, dr)
        # ride the layer loops' carry with the running layer index, as the
        # GQA ring does: each layer writes its rows in place, then attends
        # over its slab
        slot = pos % cache["ckv"].shape[2]

        def body(carry, lp):
            h, cks, krs, l = carry
            normed = L.apply_norm(cfg, lp["ln1"], h)
            q_eff, q_rope, c_new, kr_new = _mla_decode_q(cfg, lp["attn"],
                                                         normed, pos)
            cks = _ring_write(cks, c_new, l, slot)
            krs = _ring_write(krs, kr_new, l, slot)
            a = _mla_decode_attend(cfg, lp["attn"], q_eff, q_rope, cks[l],
                                   krs[l], slot_pos_new, pos)
            h = h + a.astype(normed.dtype)
            f = ffn(L.apply_norm(cfg, lp["ln2"], h), lp)
            return (h + f, cks, krs, l + 1), None

        carry = (x, cache["ckv"], cache["krope"], jnp.int32(0))
        for stack in stacks:
            carry, _ = lax.scan(body, carry, stack)
        x, cks, krs, _ = carry
        cache = dict(cache, ckv=cks, krope=krs, slot_pos=slot_pos_new,
                     pos=pos + 1)

    elif cfg.family == "ssm":
        def body(h, layer):
            lp, st, cv = layer
            normed = L.apply_norm(cfg, lp["ln"], h)
            o, st, cv = mamba2_decode(cfg, lp["mamba"], normed, st, cv)
            return h + o, (st, cv)

        x, (sts, cvs) = lax.scan(
            body, x, (params["layers"], cache["ssm_state"], cache["conv_state"]))
        cache = dict(cache, ssm_state=sts, conv_state=cvs, pos=pos + 1)

    elif cfg.family == "hybrid":
        x, cache = _decode_hybrid(cfg, params, cache, x, slot_pos_new, pos)

    elif cfg.family == "encdec":
        def body(h, layer):
            lp, kc, vc, xk, xv = layer
            normed = L.apply_norm(cfg, lp["ln1"], h)
            a, kc, vc = attention_decode(cfg, lp["attn"], normed, kc, vc,
                                         slot_pos_new, pos, rope=False)
            h = h + a
            xa = cross_attention_decode(
                cfg, lp["xattn"], L.apply_norm(cfg, lp["ln_x"], h), xk, xv,
                cfg.encoder_seq)
            h = h + xa
            f = L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, lp["ln2"], h))
            return h + f, (kc, vc)

        pos_emb = jnp.take(params["dec_pos"],
                           jnp.minimum(pos, params["dec_pos"].shape[0] - 1),
                           axis=0)
        x = x + pos_emb.astype(x.dtype)
        x, (ks, vs) = lax.scan(
            body, x,
            (params["dec_layers"], cache["k"], cache["v"],
             cache["cross_k"], cache["cross_v"]))
        cache = dict(cache, k=ks, v=vs, slot_pos=slot_pos_new, pos=pos + 1)
    else:
        raise ValueError(cfg.family)

    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = _lm_logits(cfg, params, x[:, None, :])[:, 0]
    return cache, logits


def _decode_hybrid(cfg: ModelConfig, params: Params, cache: Cache,
                   x: jnp.ndarray, slot_pos_new, pos):
    period = cfg.hybrid_period
    n_groups = cfg.n_layers // period
    n_rem = cfg.n_layers - n_groups * period
    n_shared = max(cfg.n_shared_blocks, 1)

    def regroup(t):
        return jax.tree.map(
            lambda a: a[: n_groups * period].reshape(
                (n_groups, period) + a.shape[1:]), t)

    grouped_lp = regroup(params["layers"])
    grouped_st = regroup(cache["ssm_state"])
    grouped_cv = regroup(cache["conv_state"])
    rest_lp = jax.tree.map(lambda a: a[n_groups * period:], params["layers"])
    rest_st = cache["ssm_state"][n_groups * period:]
    rest_cv = cache["conv_state"][n_groups * period:]
    shared = params["shared_blocks"]

    def ssm_one(h, layer):
        lp, st, cv = layer
        normed = L.apply_norm(cfg, lp["ln"], h)
        o, st, cv = mamba2_decode(cfg, lp["mamba"], normed, st, cv)
        return h + o, (st, cv)

    def group_body(carry, inp):
        h, g = carry
        glp, gst, gcv, kc, vc = inp
        h, (gst, gcv) = lax.scan(ssm_one, h, (glp, gst, gcv))
        sel = jax.tree.map(lambda a: a[g % n_shared], shared)
        normed = L.apply_norm(cfg, sel["ln1"], h)
        a, kc, vc = attention_decode(cfg, sel["attn"], normed, kc, vc,
                                     slot_pos_new, pos)
        h = h + a
        h = h + L.mlp(cfg, sel["mlp"], L.apply_norm(cfg, sel["ln2"], h))
        return (h, g + 1), (gst, gcv, kc, vc)

    (x, _), (sts, cvs, ks, vs) = lax.scan(
        group_body, (x, jnp.int32(0)),
        (grouped_lp, grouped_st, grouped_cv, cache["attn_k"], cache["attn_v"]))

    new_st = sts.reshape((n_groups * period,) + sts.shape[2:])
    new_cv = cvs.reshape((n_groups * period,) + cvs.shape[2:])
    if n_rem:
        x, (rst, rcv) = lax.scan(ssm_one, x, (rest_lp, rest_st, rest_cv))
        new_st = jnp.concatenate([new_st, rst], axis=0)
        new_cv = jnp.concatenate([new_cv, rcv], axis=0)

    cache = dict(cache, ssm_state=new_st, conv_state=new_cv,
                 attn_k=ks, attn_v=vs, slot_pos=slot_pos_new, pos=pos + 1)
    return x, cache
