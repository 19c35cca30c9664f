"""Prefill: forward over a prompt that also materializes the decode cache.

``prefill_32k`` cells lower exactly this — a forward pass that returns
(populated cache, next-token logits).  The cache layouts match
``decode.init_cache`` exactly, so ``decode_step`` continues from a prefill
without reshaping (asserted by tests/test_serving.py).

Ring-buffer fill: the cache keeps the last ``sb`` positions
(``sb = decode.kv_buf_len(cfg, cap)``).  Position ``p`` lives at slot
``p % sb``; for ``S >= sb`` the slots hold positions ``[S−sb, S)`` as the
permutation ``slot j ← pos S−sb+((j−S) mod sb)``, and for ``S < sb`` slots
``[S, sb)`` stay empty (``slot_pos = −1`` masks them).

**Chunked streamed prefill** (:func:`prefill_chunked`): the prompt is split
into fixed-size chunks driven by ``core/pipeline.chunk_pipeline_carried``
— chunk *k*'s forward overlaps chunk *k−1*'s cache write (the paper's bulk
``gasnet_put`` of the prompt cache turned into an ART stream; on a
sequence-sharded cache the per-chunk ring scatter *is* the wire transfer).
Each chunk attends against a full-length K/V scratch with the chunk's
absolute ``q_offset``, so every row runs the exact bulk blockwise-softmax
recipe and the resulting cache is **bit-identical** to :func:`prefill`
(asserted by tests/test_serving.py, odd chunk sizes included).  The
incremental flavor (:func:`prefill_chunk` over :func:`init_prefill_scratch`
/ :func:`scratch_to_cache`) is what the continuous-batching server admits
between decode steps.

**The chunk-carry contract** (``configs.base.chunk_carry_spec``) makes that
path total over the config zoo — every family defines what a chunk hands to
the next one, and this module implements the triple per carry kind:

* ``ring`` (GQA dense / vlm / moe) — full-length K/V scratch rows, as
  above; vlm chunks slice the frontend-embedding rows exactly like the
  bulk concat (both are row-wise).  MoE rides the same carry with
  **chunk-local capacity**: ``layers.moe_route`` bookkeeps capacity per
  call, so each chunk's drop set is computed from the chunk length —
  :func:`moe_chunk_agree_mask` states (and tests/test_zoo.py asserts) the
  equivalence bound: each MoE layer's output is bitwise equal at every
  token whose keep decisions match, and the whole forward is exact when
  they match everywhere — in particular when no row overflows either
  program.
* ``latent`` (MLA) — full-length latent ``ckv`` + shared rope-key rows;
  per-head K/V are re-expanded from the scratch each chunk (rows past the
  chunk are zeros, and causally masked contributions are *exactly* zero in
  the blockwise recipe, so the reduction is bulk's).
* ``state`` (mamba2) — **constant-size** carry: the per-layer SSD state
  (the ``ssd`` kernel's ``init_state`` resume hook) plus the (conv−1) raw
  pre-conv rows.  Bit-identical to bulk whenever interior cuts land on
  multiples of ``ssm_chunk`` (the SSD chunk walk visits the same blocks;
  ``ChunkCarrySpec.chunk_multiple`` says so and
  :func:`prefill_chunk_cuts` aligns cuts to it).
* ``hybrid`` (zamba2) — the ``state`` pair per layer plus ring rows for
  the shared attention blocks.
* ``encdec`` (whisper) — chunk 0 runs the encoder once and materializes
  the cross-K/V; decoder chunks then stream like ``ring`` rows (no rope,
  learned positions sliced at the chunk offset).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (
    ChunkCarrySpec,
    ModelConfig,
    chunk_carry_spec,
    serving_features,
)
from repro.core import pipeline as pl
from repro.models import layers as L
from repro.models.decode import kv_buf_len
from repro.models.model import (
    _lm_logits,
    _maybe_remat,
    encode,
)
from repro.models.shardctx import constrain

Params = Dict[str, Any]
Cache = Dict[str, Any]


def _slot_map(s: int, sb: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (pos_for_slot (sb,) int32 with −1 empty, gather_idx (sb,))."""
    j = jnp.arange(sb)
    if s >= sb:
        pos = s - sb + ((j - s) % sb)
        return pos.astype(jnp.int32), pos.astype(jnp.int32)
    pos = jnp.where(j < s, j, -1)
    return pos.astype(jnp.int32), jnp.maximum(pos, 0).astype(jnp.int32)


def _ring_fill(seq_t: jnp.ndarray, sb: int, seq_axis: int):
    """Scatter a (..., S, ...) sequence tensor into its ring-buffer layout."""
    s = seq_t.shape[seq_axis]
    slot_pos, idx = _slot_map(s, sb)
    filled = jnp.take(seq_t, idx, axis=seq_axis)
    if s < sb:
        # zero the empty tail so the cache has no garbage (masked anyway)
        shape = [1] * seq_t.ndim
        shape[seq_axis] = sb
        mask = (slot_pos >= 0).reshape(shape)
        filled = jnp.where(mask, filled, jnp.zeros_like(filled))
    return filled, slot_pos


# ---------------------------------------------------------------------------
# family prefills
# ---------------------------------------------------------------------------


def _ffn(cfg: ModelConfig, lp: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The layer's feed-forward: the MLP, or the MoE of a MoE layer."""
    if "moe" in lp:
        return L.moe(cfg, lp["moe"], x)
    return L.mlp(cfg, lp["mlp"], x)


def _scan_layers(cfg: ModelConfig, body, carry, params: Params, xs=()):
    """``lax.scan`` of ``body`` over the layer stacks in order: a MoE
    stack's leading dense layers (``params["dense_layers"]``), then
    ``params["layers"]``.  ``xs`` are per-layer inputs stacked over all
    layers, split to match; each layer gets ``(lp, *xs_l)`` (or ``lp``
    alone without ``xs``), and the stacked outputs are joined."""
    stacks = [params[k] for k in ("dense_layers", "layers") if k in params]
    outs, lo = [], 0
    for stack in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        sl = (tuple(jax.tree.map(lambda a: a[lo:lo + n], x) for x in xs)
              if len(stacks) > 1 else tuple(xs))
        carry, ys = lax.scan(_maybe_remat(cfg, body), carry,
                             (stack,) + sl if xs else stack)
        outs.append(ys)
        lo += n
    if len(outs) == 1:
        return carry, outs[0]
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a), *outs)


def _prefill_gqa(cfg: ModelConfig, params: Params, x: jnp.ndarray,
                 positions: jnp.ndarray, sb: int):
    def body(h, lp):
        normed = L.apply_norm(cfg, lp["ln1"], h)
        a, (k, v) = L.attention(cfg, lp["attn"], normed, positions,
                                return_kv=True)
        h = h + a
        h = h + _ffn(cfg, lp, L.apply_norm(cfg, lp["ln2"], h))
        kc, _ = _ring_fill(k, sb, seq_axis=2)
        vc, _ = _ring_fill(v, sb, seq_axis=2)
        return constrain(h, "residual"), (kc.astype(jnp.dtype(cfg.param_dtype)),
                                          vc.astype(jnp.dtype(cfg.param_dtype)))

    x, (ks, vs) = _scan_layers(cfg, body, x, params)
    slot_pos, _ = _slot_map(x.shape[1], sb)
    return x, {"k": ks, "v": vs, "slot_pos": slot_pos}


def _prefill_mla(cfg: ModelConfig, params: Params, x: jnp.ndarray,
                 positions: jnp.ndarray, sb: int):
    dt = jnp.dtype(cfg.param_dtype)

    def body(h, lp):
        normed = L.apply_norm(cfg, lp["ln1"], h)
        a, (ckv, krope) = L.mla_attention(cfg, lp["attn"], normed, positions,
                                          return_cache=True)
        h = h + a
        h = h + _ffn(cfg, lp, L.apply_norm(cfg, lp["ln2"], h))
        cc, _ = _ring_fill(ckv, sb, seq_axis=1)
        kr, _ = _ring_fill(krope, sb, seq_axis=1)
        return constrain(h, "residual"), (cc.astype(dt), kr.astype(dt))

    x, (cks, krs) = _scan_layers(cfg, body, x, params)
    slot_pos, _ = _slot_map(x.shape[1], sb)
    return x, {"ckv": cks, "krope": krs, "slot_pos": slot_pos}


def _prefill_ssm_stack(cfg: ModelConfig, stack: Params, x: jnp.ndarray):
    dt = jnp.dtype(cfg.param_dtype)

    def body(h, lp):
        normed = L.apply_norm(cfg, lp["ln"], h)
        o, (state, conv_tail) = L.mamba2_block(cfg, lp["mamba"], normed,
                                               return_state=True)
        return constrain(h + o, "residual"), (state, conv_tail.astype(dt))

    return lax.scan(_maybe_remat(cfg, body), x, stack)


def _prefill_hybrid(cfg: ModelConfig, params: Params, x: jnp.ndarray,
                    positions: jnp.ndarray, sb: int):
    dt = jnp.dtype(cfg.param_dtype)
    period = cfg.hybrid_period
    n_groups = cfg.n_layers // period
    n_rem = cfg.n_layers - n_groups * period
    n_shared = max(cfg.n_shared_blocks, 1)
    grouped = jax.tree.map(
        lambda a: a[: n_groups * period].reshape(
            (n_groups, period) + a.shape[1:]), params["layers"])
    rest = jax.tree.map(lambda a: a[n_groups * period:], params["layers"])
    shared = params["shared_blocks"]

    def group_body(carry, glayers):
        h, g = carry
        h, (st, cv) = _prefill_ssm_stack(cfg, glayers, h)
        sel = jax.tree.map(lambda a: a[g % n_shared], shared)
        normed = L.apply_norm(cfg, sel["ln1"], h)
        a, (k, v) = L.attention(cfg, sel["attn"], normed, positions,
                                return_kv=True)
        h = h + a
        h = h + L.mlp(cfg, sel["mlp"], L.apply_norm(cfg, sel["ln2"], h))
        kc, _ = _ring_fill(k, sb, seq_axis=2)
        vc, _ = _ring_fill(v, sb, seq_axis=2)
        return (constrain(h, "residual"), g + 1), (
            st, cv, kc.astype(dt), vc.astype(dt))

    (x, _), (sts, cvs, ks, vs) = lax.scan(
        _maybe_remat(cfg, group_body), (x, jnp.int32(0)), grouped)
    ssm_state = sts.reshape((n_groups * period,) + sts.shape[2:])
    conv_state = cvs.reshape((n_groups * period,) + cvs.shape[2:])
    if n_rem:
        x, (rst, rcv) = _prefill_ssm_stack(cfg, rest, x)
        ssm_state = jnp.concatenate([ssm_state, rst], axis=0)
        conv_state = jnp.concatenate([conv_state, rcv], axis=0)
    slot_pos, _ = _slot_map(x.shape[1], sb)
    return x, {"ssm_state": ssm_state, "conv_state": conv_state,
               "attn_k": ks, "attn_v": vs, "slot_pos": slot_pos}


def _prefill_encdec(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                    frontend_embeds: jnp.ndarray, sb: int):
    dt = jnp.dtype(cfg.param_dtype)
    enc = encode(cfg, params, frontend_embeds)
    x = jnp.take(params["embed"], tokens, axis=0)
    s = x.shape[1]
    x = x + lax.dynamic_slice_in_dim(params["dec_pos"], 0, s, 0).astype(x.dtype)
    dpos = jnp.arange(s)

    def body(h, lp):
        normed = L.apply_norm(cfg, lp["ln1"], h)
        a, (k, v) = L.attention(cfg, lp["attn"], normed, dpos,
                                return_kv=True)
        h = h + a
        kv = L.cross_kv(cfg, lp["xattn"], enc)
        h = h + L.attention(cfg, lp["xattn"],
                            L.apply_norm(cfg, lp["ln_x"], h),
                            dpos, causal=False, kv_override=kv)
        h = h + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, lp["ln2"], h))
        kc, _ = _ring_fill(k, sb, seq_axis=2)
        vc, _ = _ring_fill(v, sb, seq_axis=2)
        return constrain(h, "residual"), (
            kc.astype(dt), vc.astype(dt),
            kv[0].astype(dt), kv[1].astype(dt))

    x, (ks, vs, xks, xvs) = lax.scan(_maybe_remat(cfg, body), x,
                                     params["dec_layers"])
    slot_pos, _ = _slot_map(s, sb)
    return x, {"k": ks, "v": vs, "cross_k": xks, "cross_v": xvs,
               "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,                       # (B, S)
    frontend_embeds: Optional[jnp.ndarray] = None,
    *,
    cache_len: Optional[int] = None,
) -> Tuple[Cache, jnp.ndarray]:
    """Run the prompt, build the decode cache, return next-token logits.

    ``cache_len``: ring-buffer capacity (default: prompt length); the SWA
    window caps it (h2o-danube long contexts keep a 4096-slot cache).
    """
    from repro.models.model import _embed

    if cfg.family == "encdec":
        s = tokens.shape[1]
        sb = kv_buf_len(cfg, cache_len or s)
        x, cache = _prefill_encdec(cfg, params, tokens, frontend_embeds, sb)
        s_total = s
    else:
        x = constrain(_embed(cfg, params, tokens, frontend_embeds), "residual")
        s_total = x.shape[1]
        sb = kv_buf_len(cfg, cache_len or s_total)
        positions = jnp.arange(s_total)
        if cfg.family in ("dense", "vlm", "moe") and cfg.attn_type != "mla":
            x, cache = _prefill_gqa(cfg, params, x, positions, sb)
        elif cfg.attn_type == "mla":
            x, cache = _prefill_mla(cfg, params, x, positions, sb)
        elif cfg.family == "ssm":
            x, (st, cv) = _prefill_ssm_stack(cfg, params["layers"], x)
            cache = {"ssm_state": st, "conv_state": cv}
        elif cfg.family == "hybrid":
            x, cache = _prefill_hybrid(cfg, params, x, positions, sb)
        else:
            raise ValueError(cfg.family)

    x = L.apply_norm(cfg, params["final_norm"], x)
    last = constrain(x[:, -1:, :], "logit_hidden")
    logits = _lm_logits(cfg, params, last)[:, 0]
    return _finish_cache(cache, tokens.shape[0], s_total), logits


def _finish_cache(cache: Cache, batch: int, s_total: int) -> Cache:
    """Stamp the per-slot position bookkeeping (every row at ``s_total``)."""
    cache["pos"] = jnp.full((batch,), s_total, jnp.int32)
    if "slot_pos" in cache:
        cache["slot_pos"] = jnp.broadcast_to(
            cache["slot_pos"], (batch,) + cache["slot_pos"].shape[-1:])
    return cache


# ---------------------------------------------------------------------------
# chunked streamed prefill (the ART schedule on the prompt hot path)
# ---------------------------------------------------------------------------


def chunk_support(cfg: ModelConfig) -> Tuple[bool, str]:
    """Whether streamed prefill can run, with the fallback reason if not.

    The chunk-carry contract itself is total over the zoo
    (:func:`repro.configs.base.chunk_carry_spec`); the one thing that can
    gate it out at *runtime* is the attention kernel: every
    attention-bearing carry kind needs the blockwise ``jnp`` path, whose
    mid-sequence ``q_offset`` convention is what makes a chunk's rows run
    the exact bulk recipe.  Pure SSM has no attention and chunks under any
    impl.  Callers that fall back must say so (the server emits a build
    warning and a ``stats()`` signal with this reason).
    """
    spec = chunk_carry_spec(cfg)
    if spec.kind != "state":
        impl = L.resolve_attn_impl(cfg)
        if impl != "jnp":
            return False, (
                f"attn_impl resolves to {impl!r}; chunked prefill needs the "
                f"blockwise jnp path (mid-sequence q_offset)")
    return True, ""


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Boolean face of :func:`chunk_support` (capability rows live in
    ``configs.base.serving_features``; this is the runtime kernel gate)."""
    return chunk_support(cfg)[0]


def prefill_chunk_cuts(s_total: int, chunk_len: Optional[int] = None,
                       n_chunks: Optional[int] = None, *,
                       multiple: int = 1) -> List[Tuple[int, int]]:
    """Static ``(lo, hi)`` chunk boundaries over a prompt of ``s_total``.

    ``chunk_len`` cuts fixed-size chunks (ragged tail); ``n_chunks``
    delegates to ``pipeline.chunk_slices`` (near-equal cuts).  Neither
    (or a chunk covering the prompt) means one bulk chunk.

    ``multiple``: every *interior* cut lands on a multiple of it (the
    carry contract's ``chunk_multiple`` — SSD state hand-off is bit-exact
    only on ``ssm_chunk`` boundaries).  ``chunk_len`` rounds up to the
    multiple; ``n_chunks`` boundaries snap down to it (dropping cuts that
    collide — the chunk count may shrink, coverage never changes).  Both
    spellings tile ``[0, s_total)`` exactly once for every input.
    """
    m = max(1, int(multiple))
    if chunk_len:
        c = -(-max(1, int(chunk_len)) // m) * m
        return [(lo, min(lo + c, s_total)) for lo in range(0, s_total, c)]
    cuts = pl.chunk_slices(s_total, max(1, int(n_chunks or 1)))
    if m > 1 and len(cuts) > 1:
        snapped = sorted({(hi // m) * m for _, hi in cuts[:-1]})
        edges = [0] + [b for b in snapped if 0 < b < s_total] + [s_total]
        cuts = list(zip(edges[:-1], edges[1:]))
    return cuts


def _ssm_scratch(cfg: ModelConfig, n_layers: int, batch: int
                 ) -> Dict[str, jnp.ndarray]:
    """The constant-size state carry: per-layer SSD state (fp32, as the
    kernel accumulates) + the (conv−1) raw pre-conv rows (compute dtype,
    as the conv consumes them)."""
    cd = jnp.dtype(cfg.compute_dtype)
    conv_ch = (cfg.ssm_heads * cfg.ssm_head_dim
               + 2 * cfg.ssm_groups * cfg.ssm_state)
    return {
        "ssm_state": jnp.zeros(
            (n_layers, batch, cfg.ssm_heads, cfg.ssm_state,
             cfg.ssm_head_dim), jnp.float32),
        "conv_state": jnp.zeros(
            (n_layers, batch, cfg.ssm_conv - 1, conv_ch), cd),
    }


def init_prefill_scratch(cfg: ModelConfig, batch: int,
                         prompt_len: int) -> Cache:
    """The chunk-carry scratch one incremental prefill writes into.

    Per-family layout (the ``kind`` of :func:`chunk_carry_spec`):

    * ``ring`` — full-length K/V, compute dtype (the cast to the cache's
      param dtype happens at the ring fill, exactly where bulk casts);
    * ``latent`` — full-length ``ckv`` + rope-key rows;
    * ``state`` — :func:`_ssm_scratch` only: **constant size**, the
      ``prompt_len`` argument is deliberately unused;
    * ``hybrid`` — the state pair + per-shared-application ring rows;
    * ``encdec`` — decoder K/V + the one-time cross-K/V extent.

    Full-length attention scratch is what lets every chunk reduce over the
    same key extent as bulk — the structural bit-identity argument of this
    module's docstring.
    """
    ok, why = chunk_support(cfg)
    assert ok, f"{cfg.name}: {why}"
    cd = jnp.dtype(cfg.compute_dtype)
    pos = {"pos": jnp.zeros((batch,), jnp.int32)}
    spec = chunk_carry_spec(cfg)
    if spec.kind == "state":
        return {**_ssm_scratch(cfg, cfg.n_layers, batch), **pos}
    hd = cfg.resolved_head_dim
    kv_shape = (cfg.n_layers, batch, cfg.n_kv_heads, prompt_len, hd)
    if spec.kind == "latent":
        return {"ckv": jnp.zeros((cfg.n_layers, batch, prompt_len,
                                  cfg.kv_lora_rank), cd),
                "krope": jnp.zeros((cfg.n_layers, batch, prompt_len,
                                    cfg.qk_rope_dim), cd), **pos}
    if spec.kind == "hybrid":
        n_app = cfg.n_layers // cfg.hybrid_period
        app_shape = (n_app, batch, cfg.n_kv_heads, prompt_len, hd)
        return {**_ssm_scratch(cfg, cfg.n_layers, batch),
                "attn_k": jnp.zeros(app_shape, cd),
                "attn_v": jnp.zeros(app_shape, cd), **pos}
    if spec.kind == "encdec":
        xshape = (cfg.n_layers, batch, cfg.n_kv_heads, cfg.encoder_seq, hd)
        return {"k": jnp.zeros(kv_shape, cd), "v": jnp.zeros(kv_shape, cd),
                "cross_k": jnp.zeros(xshape, cd),
                "cross_v": jnp.zeros(xshape, cd), **pos}
    return {"k": jnp.zeros(kv_shape, cd), "v": jnp.zeros(kv_shape, cd),
            **pos}


def _chunk_attention(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                     kbuf: jnp.ndarray, vbuf: jnp.ndarray, lo: int):
    """The chunk-rows flavor of ``layers.attention``: q from the chunk,
    K/V written into (and attended against) the full-length scratch at the
    static offset ``lo`` — per-row the exact bulk recipe (including the
    encdec no-rope convention: whisper uses learned positions only)."""
    b, c, _ = x.shape
    hd = cfg.resolved_head_dim
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)
    positions = lo + jnp.arange(c)
    q = jnp.einsum("bsd,dh->bsh", xc, p["wq"].astype(cd))
    q = q.reshape(b, c, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = jnp.einsum("bsd,dh->bsh", xc, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dh->bsh", xc, p["wv"].astype(cd))
    k = k.reshape(b, c, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, c, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.family != "encdec":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    kbuf = lax.dynamic_update_slice_in_dim(kbuf, k, lo, axis=2)
    vbuf = lax.dynamic_update_slice_in_dim(vbuf, v, lo, axis=2)
    out = L.attention_core(cfg, q, kbuf, vbuf, causal=True,
                           window=cfg.window, q_offset=lo)
    out = out.transpose(0, 2, 1, 3).reshape(b, c, cfg.n_heads * hd)
    y = jnp.einsum("bsh,hd->bsd", out,
                   p["wo"].astype(cd)).astype(x.dtype)
    return y, kbuf, vbuf


def _chunk_body(cfg: ModelConfig, params: Params, ks: jnp.ndarray,
                vs: jnp.ndarray, x: jnp.ndarray, lo: int):
    """One chunk's forward through every layer.  ``ks``/``vs``:
    (L, B, Hkv, S, hd) compute-dtype scratch; ``x``: (B, C, D) embedded
    chunk rows at absolute positions ``[lo, lo+C)``.  Returns
    ``(ks', vs', h)`` with the chunk's K/V written in."""
    def body(h, layer):
        lp, kbuf, vbuf = layer
        normed = L.apply_norm(cfg, lp["ln1"], h)
        a, kbuf, vbuf = _chunk_attention(cfg, lp["attn"], normed,
                                         kbuf, vbuf, lo)
        h = h + a
        # a MoE with a capacity bookkeeps it per chunk: moe_route sees
        # this chunk's rows only — the documented exact-iff-no-overflow
        # bound (moe_chunk_agree_mask)
        h = h + _ffn(cfg, lp, L.apply_norm(cfg, lp["ln2"], h))
        return constrain(h, "residual"), (kbuf, vbuf)

    h, (ks, vs) = _scan_layers(cfg, body, x, params, (ks, vs))
    return ks, vs, h


def _chunk_mla_attention(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                         cbuf: jnp.ndarray, kbuf: jnp.ndarray, lo: int):
    """The chunk-rows flavor of ``layers.mla_attention``: the chunk's
    latent rows land in the full-length scratch, per-head K/V are
    re-expanded from the *whole* scratch (zero rows past the chunk expand
    to zero keys/values, all causally masked — exact no-ops in the
    blockwise recipe), and q attends at the absolute offset."""
    b, c, _ = x.shape
    h, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)
    positions = lo + jnp.arange(c)

    q = L.mla_query(cfg, p, xc)                           # (B, C, H, dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.mla_rope(cfg, q_rope.transpose(0, 2, 1, 3), positions)

    dkv = xc @ p["w_dkv"].astype(cd)
    c_kv = L.rms_norm(p["kv_norm"], dkv[..., :r], cfg.norm_eps)
    k_rope = L.mla_rope(cfg, dkv[..., r:][:, None], positions)
    cbuf = lax.dynamic_update_slice_in_dim(cbuf, c_kv, lo, axis=1)
    kbuf = lax.dynamic_update_slice_in_dim(kbuf, k_rope[:, 0], lo, axis=1)

    s_full = cbuf.shape[1]
    with jax.named_scope("mla.absorb"):
        k_nope = (cbuf @ p["w_uk"].astype(cd)).reshape(b, s_full, h, dn)
        vfull = (cbuf @ p["w_uv"].astype(cd)).reshape(b, s_full, h, dv)
    qh = jnp.concatenate([q_nope.transpose(0, 2, 1, 3), q_rope], axis=-1)
    kh = jnp.concatenate(
        [k_nope.transpose(0, 2, 1, 3),
         jnp.broadcast_to(kbuf[:, None], (b, h, s_full, dr))], axis=-1)
    vh = vfull.transpose(0, 2, 1, 3)
    with jax.named_scope("mla.attend"):
        out = L.attention_core(cfg, qh, kh, vh, causal=True,
                               scale=L.mla_softmax_scale(cfg), q_offset=lo)
    out = out.transpose(0, 2, 1, 3).reshape(b, c, h * dv)
    y = (out @ p["wo"].astype(cd)).astype(x.dtype)
    return y, cbuf, kbuf


def _chunk_mla_body(cfg: ModelConfig, params: Params, cks: jnp.ndarray,
                    krs: jnp.ndarray, x: jnp.ndarray, lo: int):
    """One chunk through an MLA stack.  ``cks``: (L, B, S, r) latent
    scratch; ``krs``: (L, B, S, dr) shared rope-key scratch."""
    def body(h, layer):
        lp, cbuf, kbuf = layer
        normed = L.apply_norm(cfg, lp["ln1"], h)
        a, cbuf, kbuf = _chunk_mla_attention(cfg, lp["attn"], normed,
                                             cbuf, kbuf, lo)
        h = h + a
        h = h + _ffn(cfg, lp, L.apply_norm(cfg, lp["ln2"], h))
        return constrain(h, "residual"), (cbuf, kbuf)

    h, (cks, krs) = _scan_layers(cfg, body, x, params, (cks, krs))
    return cks, krs, h


def _chunk_ssm_stack(cfg: ModelConfig, stack: Params, states: jnp.ndarray,
                     tails: jnp.ndarray, x: jnp.ndarray):
    """One chunk through a mamba2 stack, resuming each layer from its
    carried (SSD state, conv tail) pair — the ``ssd`` kernel's
    ``init_state`` hook plus a VALID conv over [tail ‖ chunk rows].
    Returns ``(h, states', tails')`` (constant-size carry)."""
    def body(h, layer):
        lp, st, cv = layer
        normed = L.apply_norm(cfg, lp["ln"], h)
        o, (st, cv) = L.mamba2_block(cfg, lp["mamba"], normed,
                                     return_state=True, init_state=st,
                                     conv_state=cv)
        return constrain(h + o, "residual"), (st, cv)

    h, (sts, cvs) = lax.scan(_maybe_remat(cfg, body), x,
                             (stack, states, tails))
    return h, sts, cvs


def _chunk_hybrid(cfg: ModelConfig, params: Params, scratch: Cache,
                  x: jnp.ndarray, lo: int):
    """One chunk through a zamba2 hybrid: grouped SSM stacks carry their
    state pairs, the shared attention applications ride the ring carry."""
    period = cfg.hybrid_period
    n_groups = cfg.n_layers // period
    n_rem = cfg.n_layers - n_groups * period
    n_shared = max(cfg.n_shared_blocks, 1)
    grouped = jax.tree.map(
        lambda a: a[: n_groups * period].reshape(
            (n_groups, period) + a.shape[1:]), params["layers"])
    rest = jax.tree.map(lambda a: a[n_groups * period:], params["layers"])
    shared = params["shared_blocks"]
    regroup = lambda a: a[: n_groups * period].reshape(
        (n_groups, period) + a.shape[1:])
    gst = regroup(scratch["ssm_state"])
    gcv = regroup(scratch["conv_state"])

    def group_body(carry, inp):
        h, g = carry
        glayers, st, cv, kbuf, vbuf = inp
        h, st, cv = _chunk_ssm_stack(cfg, glayers, st, cv, h)
        sel = jax.tree.map(lambda a: a[g % n_shared], shared)
        normed = L.apply_norm(cfg, sel["ln1"], h)
        a, kbuf, vbuf = _chunk_attention(cfg, sel["attn"], normed,
                                         kbuf, vbuf, lo)
        h = h + a
        h = h + L.mlp(cfg, sel["mlp"], L.apply_norm(cfg, sel["ln2"], h))
        return (constrain(h, "residual"), g + 1), (st, cv, kbuf, vbuf)

    (h, _), (gst, gcv, ks, vs) = lax.scan(
        _maybe_remat(cfg, group_body), (x, jnp.int32(0)),
        (grouped, gst, gcv, scratch["attn_k"], scratch["attn_v"]))
    ssm_state = gst.reshape((n_groups * period,) + gst.shape[2:])
    conv_state = gcv.reshape((n_groups * period,) + gcv.shape[2:])
    if n_rem:
        h, rst, rcv = _chunk_ssm_stack(
            cfg, rest, scratch["ssm_state"][n_groups * period:],
            scratch["conv_state"][n_groups * period:], h)
        ssm_state = jnp.concatenate([ssm_state, rst], axis=0)
        conv_state = jnp.concatenate([conv_state, rcv], axis=0)
    return dict(scratch, ssm_state=ssm_state, conv_state=conv_state,
                attn_k=ks, attn_v=vs), h


def _chunk_encdec(cfg: ModelConfig, params: Params, scratch: Cache,
                  tokens: jnp.ndarray, lo: int,
                  frontend_embeds: Optional[jnp.ndarray]):
    """One decoder chunk of an encoder-decoder.  Chunk 0 runs the encoder
    once and materializes every layer's cross-K/V into the scratch; later
    chunks reuse it (the "encoder-once" carry).  Decoder self-attention
    streams like the ring kind (no rope — whisper's learned positions are
    sliced at the chunk offset instead)."""
    enc = None
    if lo == 0:
        assert frontend_embeds is not None, "encdec chunk 0 needs frames"
        enc = encode(cfg, params, frontend_embeds)

    x = jnp.take(params["embed"], tokens, axis=0)
    c = x.shape[1]
    x = x + lax.dynamic_slice_in_dim(params["dec_pos"], lo, c,
                                     0).astype(x.dtype)
    dpos = lo + jnp.arange(c)

    def body(h, layer):
        lp, kbuf, vbuf, k1, v1 = layer
        normed = L.apply_norm(cfg, lp["ln1"], h)
        a, kbuf, vbuf = _chunk_attention(cfg, lp["attn"], normed,
                                         kbuf, vbuf, lo)
        h = h + a
        if enc is not None:
            # chunk 0: the same per-layer cross_kv call bulk prefill makes
            # inside its scan — later chunks reuse the materialized rows
            k1, v1, _ = L.cross_kv(cfg, lp["xattn"], enc)
        h = h + L.attention(cfg, lp["xattn"],
                            L.apply_norm(cfg, lp["ln_x"], h),
                            dpos, causal=False, kv_override=(k1, v1, None))
        h = h + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, lp["ln2"], h))
        return constrain(h, "residual"), (kbuf, vbuf, k1, v1)

    h, (ks, vs, xks, xvs) = lax.scan(
        _maybe_remat(cfg, body), x,
        (params["dec_layers"], scratch["k"], scratch["v"],
         scratch["cross_k"], scratch["cross_v"]))
    return dict(scratch, k=ks, v=vs, cross_k=xks, cross_v=xvs), h


def _embed_chunk(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                 frontend_embeds: Optional[jnp.ndarray]) -> jnp.ndarray:
    """A chunk's row-slice of ``model._embed`` — the frontend projection
    and the concat are both row-wise, so slicing fe/text rows per chunk
    reproduces the bulk rows bit for bit."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if frontend_embeds is not None and frontend_embeds.shape[1]:
        cd = jnp.dtype(cfg.compute_dtype)
        vis = (frontend_embeds.astype(cd)
               @ params["frontend_proj"].astype(cd)).astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
    return x


def _chunk_logits(cfg: ModelConfig, params: Params,
                  h: jnp.ndarray) -> jnp.ndarray:
    x = L.apply_norm(cfg, params["final_norm"], h)
    last = constrain(x[:, -1:, :], "logit_hidden")
    return _lm_logits(cfg, params, last)[:, 0]


def prefill_chunk(cfg: ModelConfig, params: Params, scratch: Cache,
                  tokens: jnp.ndarray, lo: int,
                  frontend_embeds: Optional[jnp.ndarray] = None,
                  ) -> Tuple[Cache, jnp.ndarray]:
    """One incremental prefill chunk (the server's admission step).

    ``tokens``: (B, C) — the prompt's *token* rows in ``[lo, lo+C)``;
    ``lo`` is static (each (chunk shape, offset) pair is its own jitted
    program, which is what keeps the path bit-identical to bulk).
    ``frontend_embeds``: the chunk's frontend rows — for vlm, the
    fe-row slice of the chunk (frontend rows precede text rows exactly as
    in the bulk concat); for encdec, the *full* frame tensor on chunk 0
    only (the encoder runs once).  Dispatches on the carry kind of
    :func:`chunk_carry_spec`; returns the updated scratch and the chunk's
    next-token logits (meaningful once the final chunk has run).
    """
    spec = chunk_carry_spec(cfg)
    if spec.kind == "encdec":
        new, h = _chunk_encdec(cfg, params, scratch, tokens, lo,
                               frontend_embeds)
        hi = lo + tokens.shape[1]
    else:
        x = constrain(_embed_chunk(cfg, params, tokens, frontend_embeds),
                      "residual")
        hi = lo + x.shape[1]
        if spec.kind == "latent":
            cks, krs, h = _chunk_mla_body(cfg, params, scratch["ckv"],
                                          scratch["krope"], x, lo)
            new = dict(scratch, ckv=cks, krope=krs)
        elif spec.kind == "state":
            h, sts, cvs = _chunk_ssm_stack(cfg, params["layers"],
                                           scratch["ssm_state"],
                                           scratch["conv_state"], x)
            new = dict(scratch, ssm_state=sts, conv_state=cvs)
        elif spec.kind == "hybrid":
            new, h = _chunk_hybrid(cfg, params, scratch, x, lo)
        else:
            ks, vs, h = _chunk_body(cfg, params, scratch["k"],
                                    scratch["v"], x, lo)
            new = dict(scratch, k=ks, v=vs)
    new["pos"] = jnp.full_like(scratch["pos"], hi)
    return new, _chunk_logits(cfg, params, h)


def scratch_to_cache(cfg: ModelConfig, scratch: Cache,
                     cache_len: Optional[int] = None) -> Cache:
    """Convert a *completed* prefill scratch into the decode-cache layout
    — bit-identical to the cache bulk :func:`prefill` builds.  Ring kinds
    ring-fill their sequence rows (casting to the param dtype exactly
    where bulk casts); the state kind's carry already *is* the cache."""
    dt = jnp.dtype(cfg.param_dtype)
    spec = chunk_carry_spec(cfg)

    if spec.kind == "state":
        return {"ssm_state": scratch["ssm_state"],
                "conv_state": scratch["conv_state"].astype(dt),
                "pos": scratch["pos"]}

    def fill(name, seq_axis, sb):
        filled, _ = _ring_fill(scratch[name], sb, seq_axis=seq_axis)
        return filled.astype(dt)

    if spec.kind == "latent":
        s = scratch["ckv"].shape[2]
        batch = scratch["ckv"].shape[1]
        sb = kv_buf_len(cfg, cache_len or s)
        cache = {"ckv": fill("ckv", 2, sb), "krope": fill("krope", 2, sb)}
    elif spec.kind == "hybrid":
        s = scratch["attn_k"].shape[3]
        batch = scratch["attn_k"].shape[1]
        sb = kv_buf_len(cfg, cache_len or s)
        cache = {"ssm_state": scratch["ssm_state"],
                 "conv_state": scratch["conv_state"].astype(dt),
                 "attn_k": fill("attn_k", 3, sb),
                 "attn_v": fill("attn_v", 3, sb)}
    elif spec.kind == "encdec":
        s = scratch["k"].shape[3]
        batch = scratch["k"].shape[1]
        sb = kv_buf_len(cfg, cache_len or s)
        cache = {"k": fill("k", 3, sb), "v": fill("v", 3, sb),
                 "cross_k": scratch["cross_k"].astype(dt),
                 "cross_v": scratch["cross_v"].astype(dt)}
    else:
        s = scratch["k"].shape[3]
        batch = scratch["k"].shape[1]
        sb = kv_buf_len(cfg, cache_len or s)
        cache = {"k": fill("k", 3, sb), "v": fill("v", 3, sb)}
    slot_pos, _ = _slot_map(s, sb)
    cache["slot_pos"] = slot_pos
    return _finish_cache(cache, batch, s)


def moe_chunk_agree_mask(cfg: ModelConfig, moe_params: Params,
                         x: jnp.ndarray,
                         cuts: List[Tuple[int, int]]):
    """The MoE chunk-local capacity bound, stated operationally.

    ``x``: (B, S, D) — one MoE layer's input rows; ``cuts``: the chunk
    boundaries.  Returns ``(agree, keep_bulk, keep_chunk)`` where the
    ``keep_*`` are the (B, S, K) per-(token, expert) keep decisions of
    the bulk program (capacity bookkept over S) and the chunk-local
    program (capacity bookkept per chunk), and ``agree`` (B, S) is their
    rowwise conjunction.

    **Bound**: routing logits, top-k choice, and normalized weights are
    all per-row (``layers.moe_route`` normalizes over the chosen k
    *before* applying capacity), the dispatch slot a token combines from
    holds that token's own row, and the expert FFN is row-independent —
    so capacity only decides *which* (token, expert) pairs contribute,
    and *this layer's* MoE output is bitwise equal at every token where
    ``agree`` holds.  Attention then mixes rows, so whole-forward
    identity needs agreement everywhere: when no row overflows in either
    program at any layer (``agree`` all-True throughout, e.g. a capacity
    factor ≥ ``n_experts``), chunked prefill ≡ bulk bit for bit; when
    drops differ, outputs diverge and this mask names the first culprit
    rows.  tests/test_zoo.py asserts both directions.
    """
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)
    keep_bulk = L.moe_route(cfg, moe_params["router"], xc)[2]
    keep_chunk = jnp.concatenate(
        [L.moe_route(cfg, moe_params["router"], xc[:, lo:hi])[2]
         for lo, hi in cuts], axis=1)
    agree = jnp.all(keep_bulk == keep_chunk, axis=-1)
    return agree, keep_bulk, keep_chunk


# ---------------------------------------------------------------------------
# paged KV block pool (PR 6): slot cache <-> pool blocks
# ---------------------------------------------------------------------------


def cache_to_blocks(cfg: ModelConfig, slot_cache: Cache, block_size: int):
    """Split a single-request ring cache into pool blocks.

    ``slot_cache``: the batch-1 cache :func:`prefill` /
    :func:`scratch_to_cache` builds (``k``/``v`` (L, 1, Hkv, sb, hd)).
    Returns ``(blocks_k, blocks_v, slot_pos_row, pos_row)`` with blocks
    shaped (L, sb/blk, Hkv, blk, hd) — a pure reshape of the ring layout
    (``block_size`` must divide ``sb``), so pushing the blocks into a
    pool and gathering them back via the block table reproduces the
    contiguous cache bit for bit.  These are the "finished chunk-blocks"
    a prefill rank PUTs into the decode pool (``core/pgas.BlockSegment``
    prices the one-sided writes).
    """
    k = slot_cache["k"]
    nl, b1, hkv, sb, hd = k.shape
    assert b1 == 1, k.shape
    if sb % block_size:
        raise ValueError(
            f"block_size {block_size} must divide the ring extent {sb}")
    npb = sb // block_size

    def split(a):
        blocks = a[:, 0].reshape(nl, hkv, npb, block_size, hd)
        return blocks.transpose(0, 2, 1, 3, 4)

    return (split(k), split(slot_cache["v"]),
            slot_cache["slot_pos"][0], slot_cache["pos"][0])


def scratch_to_blocks(cfg: ModelConfig, scratch: Cache, block_size: int,
                      cache_len: Optional[int] = None):
    """Ring-fill a completed prefill scratch straight into pool blocks
    (:func:`scratch_to_cache` composed with :func:`cache_to_blocks` —
    the paged flavor of the server's admission conversion)."""
    return cache_to_blocks(cfg, scratch_to_cache(cfg, scratch,
                                                 cache_len=cache_len),
                           block_size)


def seed_scratch_from_blocks(cfg: ModelConfig, scratch: Cache,
                             blocks_k: jnp.ndarray,
                             blocks_v: jnp.ndarray) -> Cache:
    """Seed a fresh prefill scratch with ``m`` cached prefix blocks.

    The prefix-cache hit path: positions ``[0, m·blk)`` of the scratch
    are restored from pool blocks instead of recomputed, and chunked
    prefill resumes at the first uncached chunk.  Valid only while the
    cached prefix never wrapped the ring (slot ``j`` == position ``j`` —
    the server's sharing guard), and bit-exact when the pool dtype equals
    the compute dtype (the reduced/test configs; otherwise the prefix
    K/V round-trips through the param dtype, ulp-level like any
    cross-program reshard).
    """
    nl, m, hkv, blk, hd = blocks_k.shape
    cd = jnp.dtype(cfg.compute_dtype)

    def merge(buf, blocks):
        flat = blocks.transpose(0, 2, 1, 3, 4).reshape(nl, hkv, m * blk, hd)
        return lax.dynamic_update_slice_in_dim(
            buf, flat[:, None].astype(cd), 0, axis=3)

    return dict(scratch, k=merge(scratch["k"], blocks_k),
                v=merge(scratch["v"], blocks_v))


def prefill_chunked(
    cfg: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,                       # (B, S)
    frontend_embeds: Optional[jnp.ndarray] = None,
    *,
    cache_len: Optional[int] = None,
    chunk_len: Optional[int] = None,
    n_chunks: Optional[int] = None,
) -> Tuple[Cache, jnp.ndarray]:
    """Chunked streamed prefill: :func:`prefill`, as an ART pipeline.

    The prompt runs in fixed-size chunks through
    ``pipeline.chunk_pipeline_carried``: chunk *k*'s forward (the carried
    compute) overlaps chunk *k−1*'s ring-cache scatter (the transfer — on a
    sequence-sharded cache that scatter is the wire write, the bulk
    ``gasnet_put`` of the paper's serving shape split into ART chunks).
    Cache and logits are bit-identical to bulk :func:`prefill` — every row
    runs the same blockwise recipe against the same key extent (module
    docstring) — asserted across odd chunk sizes by tests/test_serving.py
    and across the whole zoo by tests/test_zoo.py (MoE: exact under the
    no-overflow bound of :func:`moe_chunk_agree_mask`).

    The ``ring`` carry kinds run the pipelined schedule below (the growing
    K/V slab's ring scatter is the wire write worth overlapping); the
    other carries walk :func:`prefill_chunk` sequentially — their per-chunk
    hand-off is the carry itself, which the server streams anyway.  Cuts
    align to the carry's ``chunk_multiple`` (SSD state hand-off is exact
    on ``ssm_chunk`` boundaries).  Archs gated out by
    :func:`chunk_support` fall back to bulk.
    """
    from repro.models.model import _embed

    spec = chunk_carry_spec(cfg)
    s_total = (tokens.shape[1] + (cfg.frontend_tokens
                                  if cfg.frontend and cfg.family == "vlm"
                                  else 0))
    cuts = prefill_chunk_cuts(s_total, chunk_len, n_chunks,
                              multiple=spec.chunk_multiple)
    if len(cuts) <= 1 or not supports_chunked_prefill(cfg):
        return prefill(cfg, params, tokens, frontend_embeds,
                       cache_len=cache_len)

    if spec.kind != "ring":
        batch = tokens.shape[0]
        scratch = init_prefill_scratch(cfg, batch, s_total)
        logits = None
        for lo, hi in cuts:
            if cfg.family == "encdec":
                fe = frontend_embeds if lo == 0 else None
                sl = tokens[:, lo:hi]
            else:
                fe, sl = None, tokens[:, lo:hi]
            scratch, logits = prefill_chunk(cfg, params, scratch, sl, lo,
                                            frontend_embeds=fe)
        return scratch_to_cache(cfg, scratch, cache_len=cache_len), logits

    batch = tokens.shape[0]
    dt = jnp.dtype(cfg.param_dtype)
    sb = kv_buf_len(cfg, cache_len or s_total)
    x_full = constrain(_embed(cfg, params, tokens, frontend_embeds),
                       "residual")
    scratch = init_prefill_scratch(cfg, batch, s_total)

    def compute(k, carry):
        ks, vs = carry
        lo, hi = cuts[k]
        ks, vs, h = _chunk_body(cfg, params, ks, vs, x_full[:, lo:hi], lo)
        # the payload the "wire" carries: this chunk's K/V slab (+ the
        # residual tail that only the final chunk's logits consume)
        return (ks[:, :, :, lo:hi], vs[:, :, :, lo:hi], h), (ks, vs)

    def consume(state, k, arrived):
        ring_k, ring_v, _ = state
        ck, cv, h = arrived
        lo, hi = cuts[k]
        # ring slots of positions [lo, hi); a chunk longer than the ring
        # keeps only its last sb positions (earlier ones would be
        # overwritten within the chunk anyway)
        first = max(lo, hi - sb)
        slots = jnp.asarray([p % sb for p in range(first, hi)], jnp.int32)
        ring_k = ring_k.at[:, :, :, slots].set(
            ck[:, :, :, first - lo:].astype(dt))
        ring_v = ring_v.at[:, :, :, slots].set(
            cv[:, :, :, first - lo:].astype(dt))
        return ring_k, ring_v, h

    hd = cfg.resolved_head_dim
    ring_shape = (cfg.n_layers, batch, cfg.n_kv_heads, sb, hd)
    init = (jnp.zeros(ring_shape, dt), jnp.zeros(ring_shape, dt), None)
    (ring_k, ring_v, h_last), _ = pl.chunk_pipeline_carried(
        len(cuts), compute, lambda k, payload: payload, consume,
        carry=(scratch["k"], scratch["v"]), init=init)

    slot_pos, _ = _slot_map(s_total, sb)
    cache = _finish_cache(
        {"k": ring_k, "v": ring_v, "slot_pos": slot_pos}, batch, s_total)
    return cache, _chunk_logits(cfg, params, h_last)
