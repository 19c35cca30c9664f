"""Streamed serving: chunked prefill ≡ bulk (bitwise), EP decode ≡
dense-combine per transport, donation-clean step builders, and the
ring-buffer wraparound properties the scheduler relies on.

The bit-identity discipline (PR 2): a streamed schedule partitions the
bulk payload and runs the identical per-row recipe, so results must be
*bit*-equal, not allclose — asserted here per entry point, odd chunk
sizes and ring wraparound included.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.dist.steps import (
    StepConfig,
    TransportPolicy,
    build_prefill_chunk_step,
    build_prefill_step,
    build_serve_step,
    build_slot_write_step,
)
from repro.models.decode import (
    decode_step,
    init_cache,
    init_paged_cache,
    kv_buf_len,
    paged_slot_blocks,
    supports_paged,
)
from repro.models.model import init_params
from repro.models.prefill import (
    cache_to_blocks,
    chunk_support,
    init_prefill_scratch,
    prefill,
    prefill_chunk,
    prefill_chunk_cuts,
    prefill_chunked,
    scratch_to_cache,
    supports_chunked_prefill,
)
from repro.runtime.server import BlockPool, Server, ServerConfig


def _setup(name, **overrides):
    cfg = get_config(name).reduced()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _tokens(cfg, b, s, key=1):
    return jax.random.randint(jax.random.PRNGKey(key), (b, s), 0,
                              cfg.vocab_size)


def _assert_tree_equal(a, b, msg=""):
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"{msg} leaf {k!r}")


class TestChunkedPrefill:
    """prefill_chunked ≡ prefill, bit for bit — cache and logits."""

    @pytest.mark.parametrize("n_chunks", [2, 3, 5, 13])
    def test_bit_identical_odd_chunks(self, n_chunks):
        cfg, params = _setup("smollm-360m")
        toks = _tokens(cfg, 2, 13)
        bulk_cache, bulk_logits = prefill(cfg, params, toks, cache_len=32)
        cache, logits = prefill_chunked(cfg, params, toks, cache_len=32,
                                        n_chunks=n_chunks)
        _assert_tree_equal(bulk_cache, cache, f"n_chunks={n_chunks}")
        np.testing.assert_array_equal(np.asarray(bulk_logits),
                                      np.asarray(logits))

    def test_windowed_ring_wraparound(self):
        """Chunk boundaries crossing the SWA ring (sb < S) stay exact."""
        cfg, params = _setup("h2o-danube-1.8b")
        assert cfg.window and cfg.window < 17
        toks = _tokens(cfg, 1, 17)
        bulk_cache, bulk_logits = prefill(cfg, params, toks, cache_len=17)
        cache, logits = prefill_chunked(cfg, params, toks, cache_len=17,
                                        n_chunks=5)
        assert cache["k"].shape[3] == cfg.window     # ring, not 17
        _assert_tree_equal(bulk_cache, cache, "windowed")
        np.testing.assert_array_equal(np.asarray(bulk_logits),
                                      np.asarray(logits))

    def test_incremental_scratch_path(self):
        """The server's chunk-step flavor reassembles the bulk cache."""
        cfg, params = _setup("smollm-360m")
        toks = _tokens(cfg, 2, 11)
        bulk_cache, bulk_logits = prefill(cfg, params, toks, cache_len=24)
        scratch = init_prefill_scratch(cfg, 2, 11)
        logits = None
        for lo, hi in prefill_chunk_cuts(11, chunk_len=4):
            scratch, logits = prefill_chunk(cfg, params, scratch,
                                            toks[:, lo:hi], lo)
        cache = scratch_to_cache(cfg, scratch, cache_len=24)
        _assert_tree_equal(bulk_cache, cache, "incremental")
        np.testing.assert_array_equal(np.asarray(bulk_logits),
                                      np.asarray(logits))

    def test_decode_continues_identically(self):
        """Decoding from a chunked-prefill cache == from the bulk cache."""
        cfg, params = _setup("smollm-360m")
        toks = _tokens(cfg, 2, 9)
        ca, la = prefill(cfg, params, toks, cache_len=16)
        cb, lb = prefill_chunked(cfg, params, toks, cache_len=16,
                                 n_chunks=4)
        nxt = jnp.argmax(la, -1).astype(jnp.int32)
        ca, la2 = decode_step(cfg, params, ca, nxt)
        cb, lb2 = decode_step(cfg, params, cb, nxt)
        np.testing.assert_array_equal(np.asarray(la2), np.asarray(lb2))

    def test_pallas_attn_gated_falls_back_to_bulk(self):
        """A forced fused-attention (pallas) impl can't take the chunk
        path's mid-sequence ``q_offset``, so the gate names that reason
        and ``prefill_chunked`` falls back to bulk — while pure-SSM archs
        chunk under *any* impl (their carry is SSD state, not
        attention)."""
        cfg, params = _setup("smollm-360m", attn_impl="pallas")
        ok, why = chunk_support(cfg)
        assert not ok and "pallas" in why
        assert not supports_chunked_prefill(cfg)
        assert supports_chunked_prefill(get_config("mamba2-2.7b").reduced())
        toks = _tokens(cfg, 1, 8)
        ca, la = prefill(cfg, params, toks, cache_len=16)
        cb, lb = prefill_chunked(cfg, params, toks, cache_len=16,
                                 n_chunks=4)
        _assert_tree_equal(ca, cb, "fallback")
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    def test_cuts_partition_exactly(self):
        assert prefill_chunk_cuts(10, chunk_len=4) == [(0, 4), (4, 8),
                                                       (8, 10)]
        for s in (1, 7, 16):
            for c in (1, 3, 5, 20):
                cuts = prefill_chunk_cuts(s, chunk_len=c)
                assert cuts[0][0] == 0 and cuts[-1][1] == s
                assert all(a[1] == b[0] for a, b in zip(cuts, cuts[1:]))


class TestChunkedPrefillStep:
    """The jitted, sharded flavors (dist/steps.py) keep bit-identity."""

    @pytest.mark.parametrize("chunks", [3, 4])
    def test_prefill_step_chunks_bit_identical(self, mesh22, chunks):
        """With a fixed residual sharding (SP off) the chunked and bulk
        jitted programs are bit-identical; SP resharding (seq % tp differs
        per chunk) perturbs GSPMD reduction placement at the float-ulp
        level, so that flavor asserts tightly instead."""
        cfg = get_config("smollm-360m").reduced()
        from repro.dist.steps import build_init
        for sp, exact in ((False, True), (True, False)):
            scfg = StepConfig(sequence_parallel=sp)
            init_fn, _ = build_init(cfg, mesh22, scfg)
            params, _ = init_fn(jax.random.PRNGKey(0))
            toks = _tokens(cfg, 4, 16, key=2)
            bulk = build_prefill_step(cfg, mesh22, scfg, batch=4,
                                      seq_len=16)
            chunked = build_prefill_step(cfg, mesh22, scfg, batch=4,
                                         seq_len=16, chunks=chunks)
            ca, la = bulk.fn(params, toks)
            cb, lb = chunked.fn(params, toks)
            if exact:
                _assert_tree_equal(jax.device_get(ca), jax.device_get(cb),
                                   f"chunks={chunks}")
                np.testing.assert_array_equal(np.asarray(la),
                                              np.asarray(lb))
            else:
                for k in ca:
                    np.testing.assert_allclose(
                        np.asarray(ca[k]), np.asarray(cb[k]),
                        rtol=1e-5, atol=1e-5, err_msg=k)
                np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                           rtol=1e-5, atol=1e-5)

    def test_chunk_step_and_slot_write(self, mesh22):
        """build_prefill_chunk_step + build_slot_write_step reproduce a
        row of the batched cache exactly — against the *sharded* bulk
        prefill step (sharded-vs-unsharded differs by TP partial-sum
        order; SP off fixes the residual sharding across chunk shapes)."""
        cfg = get_config("smollm-360m").reduced()
        scfg = StepConfig(sequence_parallel=False)
        from repro.dist.sharding import to_shardings
        from repro.dist.steps import build_init
        init_fn, _ = build_init(cfg, mesh22, scfg)
        params, _ = init_fn(jax.random.PRNGKey(0))
        prompt = _tokens(cfg, 1, 10, key=3)

        writer = build_slot_write_step(cfg, mesh22, batch=4, max_seq=32)
        cache = jax.jit(lambda: init_cache(cfg, 4, 32),
                        out_shardings=to_shardings(
                            mesh22, writer.in_specs[0]))()

        scratch = None
        logits = None
        for lo, hi in prefill_chunk_cuts(10, chunk_len=4):
            bundle = build_prefill_chunk_step(cfg, mesh22, scfg, batch=1,
                                              prompt_len=10, lo=lo,
                                              chunk_len=hi - lo)
            if scratch is None:
                scratch = jax.jit(
                    lambda: init_prefill_scratch(cfg, 1, 10),
                    out_shardings=to_shardings(mesh22,
                                               bundle.in_specs[1]))()
            scratch, logits = bundle.fn(params, scratch,
                                        prompt[:, lo:hi])
        slot_cache = jax.jit(
            lambda s: scratch_to_cache(cfg, s, cache_len=32),
            out_shardings=to_shardings(mesh22, writer.in_specs[1]))(scratch)
        cache = writer.fn(cache, slot_cache, jnp.int32(2))

        # reference: the sharded bulk prefill step.  The chunk path runs as
        # *separate* jitted programs (per chunk + convert + write), and
        # GSPMD partitions each program's einsum reductions independently,
        # so cross-program equality is ulp-tight, not bitwise (the bitwise
        # claims live in TestChunkedPrefill, same-program).
        ref_bundle = build_prefill_step(cfg, mesh22, scfg, batch=1,
                                        seq_len=10, cache_len=32)
        ref_cache, ref_logits = ref_bundle.fn(params, prompt)
        got = jax.device_get(cache)
        ref = jax.device_get(ref_cache)
        np.testing.assert_allclose(np.asarray(got["k"][:, 2]),
                                   np.asarray(ref["k"][:, 0]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got["slot_pos"][2]),
                                      np.asarray(ref["slot_pos"][0]))
        assert int(got["pos"][2]) == 10
        # untouched rows stay empty
        assert int(got["pos"][0]) == 0
        assert np.all(np.asarray(got["slot_pos"][0]) == -1)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits),
                                   rtol=1e-5, atol=1e-6)


class TestPerSlotDecode:
    """Per-slot positions: cache rows advance independently."""

    def test_rows_decode_at_different_positions(self):
        """A batch whose rows were prefilled to different lengths decodes
        each row exactly as its own single-request run."""
        cfg, params = _setup("smollm-360m")
        pa = _tokens(cfg, 1, 5, key=4)
        pb = _tokens(cfg, 1, 9, key=5)
        ca, la = prefill(cfg, params, pa, cache_len=16)
        cb, lb = prefill(cfg, params, pb, cache_len=16)
        # merge the two single-request caches into one 2-row cache
        merged = {}
        for k in ca:
            ax = 0 if k in ("pos", "slot_pos") else 1
            merged[k] = jnp.concatenate([ca[k], cb[k]], axis=ax)
        toks = jnp.concatenate([jnp.argmax(la, -1),
                                jnp.argmax(lb, -1)]).astype(jnp.int32)
        for _ in range(3):
            merged, lm = decode_step(cfg, params, merged, toks)
            ca, la1 = decode_step(cfg, params, ca, toks[:1])
            cb, lb1 = decode_step(cfg, params, cb, toks[1:])
            assert np.asarray(merged["pos"]).tolist() == \
                [int(ca["pos"][0]), int(cb["pos"][0])]
            toks = jnp.argmax(lm, -1).astype(jnp.int32)
            # batched rows match the single-request argmax choices
            assert int(toks[0]) == int(jnp.argmax(la1, -1)[0])
            assert int(toks[1]) == int(jnp.argmax(lb1, -1)[0])


def _xs_ys_decode_step(cfg, params, cache, tokens):
    """The decode step as it was before the ring rode the loop carry,
    kept as the reference: each layer's ring slab goes through the layer
    scan as ``xs``, takes the new rows by a vmapped update slice, and
    comes back as ``ys``.  Dense GQA with a contiguous ring only."""
    from jax import lax

    from repro.models import layers as L
    from repro.models.decode import _masked_softmax_attend
    from repro.models.model import _lm_logits

    pos = cache["pos"]
    b = tokens.shape[0]
    hd, hkv, hq = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.n_heads
    cd = jnp.dtype(cfg.compute_dtype)
    sb = cache["slot_pos"].shape[1]
    slot_pos_new = cache["slot_pos"].at[jnp.arange(b), pos % sb].set(pos)

    def row_update(buf, new, slot):
        def one(bb, n, s):
            return lax.dynamic_update_slice(bb, n, (0, s, 0))
        return jax.vmap(one)(buf, new.astype(buf.dtype), slot)

    def attention(p, x, kc, vc):
        xc = x.astype(cd)
        q = (xc @ p["wq"].astype(cd)).reshape(b, hq, hd)
        k = (xc @ p["wk"].astype(cd)).reshape(b, hkv, hd)
        v = (xc @ p["wv"].astype(cd)).reshape(b, hkv, hd)
        posv = pos[:, None, None]
        q = L.apply_rope(q[:, :, None, :], posv, cfg.rope_theta)[:, :, 0]
        k = L.apply_rope(k[:, :, None, :], posv, cfg.rope_theta)[:, :, 0]
        kc = row_update(kc, k[:, :, None, :], pos % sb)
        vc = row_update(vc, v[:, :, None, :], pos % sb)
        qg = q.reshape(b, hkv, hq // hkv, hd).astype(jnp.float32) * hd ** -0.5
        scores = jnp.einsum("bkgd,bksd->bkgs", qg, kc.astype(jnp.float32))
        out = _masked_softmax_attend(scores, vc, slot_pos_new, pos,
                                     cfg.window)
        out = out.reshape(b, hq * hd).astype(cd)
        return (out @ p["wo"].astype(cd)).astype(x.dtype), kc, vc

    def body(h, layer):
        lp, kc, vc = layer
        a, kc, vc = attention(lp["attn"], L.apply_norm(cfg, lp["ln1"], h),
                              kc, vc)
        h = h + a
        return h + L.mlp(cfg, lp["mlp"], L.apply_norm(cfg, lp["ln2"], h)), \
            (kc, vc)

    x = jnp.take(params["embed"], tokens, axis=0)
    x, (ks, vs) = lax.scan(body, x, (params["layers"], cache["k"],
                                     cache["v"]))
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = _lm_logits(cfg, params, x[:, None, :])[:, 0]
    return dict(cache, k=ks, v=vs, slot_pos=slot_pos_new, pos=pos + 1), \
        logits


class TestInPlaceRingDecode:
    """The contiguous GQA decode step writes each row's one new K/V
    vector per layer into the ring it carries, and leaves every other
    entry alone; bit-identical to the ``xs``/``ys`` formulation."""

    @staticmethod
    def _filled_cache(cfg, positions, max_seq, key):
        """A ring full of random K/V whose ``slot_pos`` holds, per row,
        the last ``S_buf`` positions before that row's ``pos``."""
        b = len(positions)
        cache = init_cache(cfg, b, max_seq)
        sb = cache["slot_pos"].shape[1]
        kk, kv = jax.random.split(jax.random.PRNGKey(key))
        cache["k"] = jax.random.normal(kk, cache["k"].shape).astype(
            cache["k"].dtype)
        cache["v"] = jax.random.normal(kv, cache["v"].shape).astype(
            cache["v"].dtype)
        slot_pos = np.full((b, sb), -1, np.int32)
        for r, p in enumerate(positions):
            for q in range(max(0, p - sb), p):
                slot_pos[r, q % sb] = q
        cache["slot_pos"] = jnp.asarray(slot_pos)
        cache["pos"] = jnp.asarray(positions, jnp.int32)
        return cache

    # rows at different positions: fresh, mid-ring, at the ring's last
    # slot, and wrapped past it (old entries overwritten in place)
    @pytest.mark.parametrize("name,positions,overrides", [
        ("smollm-360m", [0, 5, 15, 21], {}),
        ("smollm-360m", [1, 9, 16, 30],
         {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}),
        ("h2o-danube-1.8b", [2, 7, 8, 13], {}),
    ])
    def test_only_new_slots_change_and_equal_xs_ys(self, name, positions,
                                                   overrides):
        cfg, params = _setup(name, **overrides)
        max_seq = 16
        cache = self._filled_cache(cfg, positions, max_seq, key=11)
        sb = cache["slot_pos"].shape[1]
        assert any(p >= sb for p in positions)  # one row wraps the ring
        toks = jnp.asarray([3, 17, 42, 99], jnp.int32)
        before = jax.tree.map(np.asarray, cache)

        ref_cache, ref_logits = jax.jit(
            lambda c, t: _xs_ys_decode_step(cfg, params, c, t))(cache, toks)
        new_cache, logits = jax.jit(
            lambda c, t: decode_step(cfg, params, c, t))(cache, toks)

        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        _assert_tree_equal(new_cache, ref_cache, "carry vs xs/ys")
        for leaf in ("k", "v"):
            got = np.asarray(new_cache[leaf])
            changed = np.zeros(got.shape[:2] + (sb,), bool)
            changed[:, np.arange(len(positions)),
                    np.asarray(positions) % sb] = True
            keep = ~changed[:, :, None, :, None].repeat(
                got.shape[2], 2).repeat(got.shape[4], 4)
            np.testing.assert_array_equal(got[keep], before[leaf][keep],
                                          err_msg=f"{leaf} outside new slots")
            # the new slot of every row and layer did take the new vector
            assert (got[~keep] != before[leaf][~keep]).any(), leaf


EP_TRANSPORTS = ("ring", "bidir", "auto")


class TestEPDecode:
    """Latency-mode EP decode over the conduit all_to_all."""

    def _mesh_ep(self):
        return jax.make_mesh((4,), ("expert",),
                             axis_types=(jax.sharding.AxisType.Auto,))

    def _setup_grok(self, mesh):
        from repro.dist.sharding import param_pspecs, to_shardings
        cfg = get_config("grok-1-314b").reduced()
        shape = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))
        psh = to_shardings(mesh, param_pspecs(cfg, mesh, shape))
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=psh)(jax.random.PRNGKey(0))
        return cfg, params

    def _decode_logits(self, cfg, params, mesh, transport, steps=2):
        scfg = StepConfig(transport=TransportPolicy(moe=transport))
        bundle = build_serve_step(cfg, mesh, scfg, batch=4, max_seq=32)
        from repro.dist.sharding import to_shardings
        cache = jax.jit(lambda: init_cache(cfg, 4, 32),
                        out_shardings=to_shardings(
                            mesh, bundle.in_specs[1]))()
        toks = jnp.asarray([1, 7, 3, 5], jnp.int32)
        for _ in range(steps):
            cache, logits = bundle.fn(params, cache, toks)
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
        return np.asarray(logits)

    def test_ep_decode_matches_dense_combine(self):
        mesh = self._mesh_ep()
        cfg, params = self._setup_grok(mesh)
        dense = self._decode_logits(cfg, params, mesh, "xla")
        ep = self._decode_logits(cfg, params, mesh, "ring")
        np.testing.assert_allclose(ep, dense, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("transport", ["bidir", "auto"])
    def test_ep_decode_bitwise_across_transports(self, transport):
        """Per PR-2 discipline: every conduit transport carries the same
        payload — EP decode results are bit-identical across them."""
        mesh = self._mesh_ep()
        cfg, params = self._setup_grok(mesh)
        ref = self._decode_logits(cfg, params, mesh, "ring")
        got = self._decode_logits(cfg, params, mesh, transport)
        np.testing.assert_array_equal(got, ref)

    def test_indivisible_batch_keeps_dense_combine(self, mesh22):
        """Without a usable expert axis (or batch), the serve step keeps
        the dense-combine fallback and still runs."""
        from repro.dist.sharding import param_pspecs, to_shardings
        cfg = get_config("grok-1-314b").reduced()
        shape = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))
        psh = to_shardings(mesh22, param_pspecs(cfg, mesh22, shape))
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=psh)(jax.random.PRNGKey(0))
        scfg = StepConfig(transport=TransportPolicy(moe="ring"))
        bundle = build_serve_step(cfg, mesh22, scfg, batch=3, max_seq=16)
        cache = jax.jit(lambda: init_cache(cfg, 3, 16),
                        out_shardings=to_shardings(
                            mesh22, bundle.in_specs[1]))()
        cache, logits = bundle.fn(params, cache,
                                  jnp.asarray([1, 2, 3], jnp.int32))
        assert logits.shape == (3, cfg.vocab_size)


class TestFrontendServing:
    def test_vlm_requests_carry_embeds(self, mesh22):
        """Frontend (vlm) archs serve through real per-slot prefill with
        per-request embeddings (bulk admission here; the chunked flavor
        is covered zoo-wide by tests/test_zoo.py)."""
        from repro.dist.sharding import param_pspecs, to_shardings
        from repro.runtime.server import Server, ServerConfig
        cfg = get_config("internvl2-2b").reduced()
        shape = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))
        psh = to_shardings(mesh22, param_pspecs(cfg, mesh22, shape))
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=psh)(jax.random.PRNGKey(0))
        srv = Server(cfg, params, mesh22, srv=ServerConfig(
            max_batch=2, max_seq=64, max_new_tokens=2))
        rng = np.random.default_rng(0)
        for _ in range(2):
            srv.submit(rng.integers(0, cfg.vocab_size, size=6),
                       frontend_embeds=rng.normal(
                           size=(cfg.frontend_tokens, cfg.frontend_dim)))
        srv.run()
        assert len(srv.done) == 2
        assert all(len(r.out_tokens) == 2 for r in srv.done)
        with pytest.raises(AssertionError):
            srv.submit(rng.integers(0, cfg.vocab_size, size=6))


class TestSampledServeStep:
    def test_sample_ids_equal_argmax_logits(self, mesh22):
        cfg = get_config("smollm-360m").reduced()
        scfg = StepConfig()
        from repro.dist.sharding import to_shardings
        from repro.dist.steps import build_init
        init_fn, _ = build_init(cfg, mesh22, scfg)
        params, _ = init_fn(jax.random.PRNGKey(0))
        logit_b = build_serve_step(cfg, mesh22, scfg, batch=4, max_seq=16)
        sample_b = build_serve_step(cfg, mesh22, scfg, batch=4,
                                    max_seq=16, sample=True)
        toks = jnp.asarray([3, 1, 4, 1], jnp.int32)
        c1 = jax.jit(lambda: init_cache(cfg, 4, 16),
                     out_shardings=to_shardings(
                         mesh22, logit_b.in_specs[1]))()
        c2 = jax.jit(lambda: init_cache(cfg, 4, 16),
                     out_shardings=to_shardings(
                         mesh22, sample_b.in_specs[1]))()
        c1, logits = logit_b.fn(params, c1, toks)
        c2, ids = sample_b.fn(params, c2, toks)
        assert ids.dtype == jnp.int32 and ids.shape == (4,)
        np.testing.assert_array_equal(
            np.asarray(ids), np.asarray(jnp.argmax(logits, -1)))
        _assert_tree_equal(jax.device_get(c1), jax.device_get(c2),
                           "sampled step cache")


class TestRingBufferProperties:
    """Hypothesis: slot_pos masking exactly at and across the window
    boundary, and chunked ≡ bulk across drawn odd chunk sizes."""

    @settings(max_examples=12, deadline=None)
    @given(s=st.integers(1, 20), d=st.integers(0, 6))
    def test_slot_pos_tracks_last_sb_positions(self, s, d):
        """After prefilling ``s`` tokens and decoding ``d`` more, the ring
        holds exactly the last ``min(pos, sb)`` positions — wraparound at
        and across the ``window`` boundary included."""
        cfg, params = _setup("h2o-danube-1.8b")
        sb = kv_buf_len(cfg, 24)
        toks = _tokens(cfg, 1, s + d + 1, key=6)
        cache, _ = prefill(cfg, params, toks[:, :s], cache_len=24)
        for t in range(d):
            cache, _ = decode_step(cfg, params, cache, toks[:, s + t])
        pos = s + d
        slot_pos = np.asarray(cache["slot_pos"][0])
        expect = np.full((sb,), -1, np.int64)
        for p in range(max(0, pos - sb), pos):
            expect[p % sb] = p
        np.testing.assert_array_equal(slot_pos, expect)

    @settings(max_examples=8, deadline=None)
    @given(s=st.integers(2, 14), n=st.integers(2, 7))
    def test_chunked_equals_bulk_drawn_sizes(self, s, n):
        cfg, params = _setup("smollm-360m")
        toks = _tokens(cfg, 1, s, key=100 + s)
        ca, la = prefill(cfg, params, toks, cache_len=16)
        cb, lb = prefill_chunked(cfg, params, toks, cache_len=16,
                                 n_chunks=n)
        _assert_tree_equal(ca, cb, f"s={s} n={n}")
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    def test_window_masks_exactly_at_boundary(self):
        """A key exactly ``window`` back is masked; ``window−1`` back is
        visible (the ``slot_pos > pos − window`` edge)."""
        from repro.models.decode import _valid_slots
        w = 4
        pos = jnp.asarray([10])
        slot_pos = jnp.asarray([[6, 7, 8, 9, 10, -1]])
        valid = np.asarray(_valid_slots(slot_pos, pos, w)[0])
        # pos-w = 6 masked (> is strict), 7..10 visible, empty masked
        assert valid.tolist() == [False, True, True, True, True, False]


def _install_contiguous(cache, slot_cache, i):
    """Write a batch-1 ring cache into row ``i`` of a batched cache."""
    out = dict(cache)
    for k in ("k", "v"):
        out[k] = cache[k].at[:, i].set(slot_cache[k][:, 0])
    out["slot_pos"] = cache["slot_pos"].at[i].set(slot_cache["slot_pos"][0])
    out["pos"] = cache["pos"].at[i].set(slot_cache["pos"][0])
    return out


def _install_paged(cache, blocks, i, dst):
    """Deposit a slot's blocks at pool ids ``dst`` and map row ``i``."""
    bk, bv, slot_pos_row, pos_row = blocks
    out = dict(cache)
    dst = jnp.asarray(dst, jnp.int32)
    out["kp"] = cache["kp"].at[:, dst].set(bk)
    out["vp"] = cache["vp"].at[:, dst].set(bv)
    out["block_ids"] = cache["block_ids"].at[i].set(dst)
    out["slot_pos"] = cache["slot_pos"].at[i].set(slot_pos_row)
    out["pos"] = cache["pos"].at[i].set(pos_row)
    return out


class TestPagedDecode:
    """Paged decode ≡ contiguous decode, bitwise on every active row.

    The gather of the block table reconstructs *exactly* the contiguous
    ring layout (``block_size`` divides ``kv_buf_len``), so active-row
    logits must be bit-equal — across block sizes, SWA ring wraparound,
    and shared-prefix block aliasing.  Idle rows park on a private
    reserved block and are excluded: their garbage internals diverge by
    design and their outputs are never read.
    """

    def _decode_pair(self, cfg, params, cont, paged, rows, steps=6):
        """Decode both caches in lockstep; assert bitwise equality on
        ``rows`` each step and feed the (identical) argmax back in."""
        batch = cont["pos"].shape[0]
        toks = jnp.zeros((batch,), jnp.int32)
        for t in range(steps):
            cont, la = decode_step(cfg, params, cont, toks)
            paged, lb = decode_step(cfg, params, paged, toks)
            for r in rows:
                np.testing.assert_array_equal(
                    np.asarray(la[r]), np.asarray(lb[r]),
                    err_msg=f"row {r} step {t}")
            np.testing.assert_array_equal(
                np.asarray(cont["slot_pos"])[list(rows)],
                np.asarray(paged["slot_pos"])[list(rows)])
            nxt = np.zeros((batch,), np.int32)
            for r in rows:
                nxt[r] = int(jnp.argmax(la[r]))
            toks = jnp.asarray(nxt)
        return cont, paged

    @pytest.mark.parametrize("blk", [2, 8])
    def test_bit_identical_across_block_sizes(self, blk):
        cfg, params = _setup("smollm-360m")
        assert supports_paged(cfg)
        max_seq = 16
        npb = paged_slot_blocks(cfg, max_seq, blk)
        slot_cache, _ = prefill(cfg, params, _tokens(cfg, 1, 7, key=7),
                                cache_len=max_seq)
        cont = _install_contiguous(init_cache(cfg, 2, max_seq),
                                   slot_cache, 1)
        paged = _install_paged(
            init_paged_cache(cfg, 2, max_seq, blk, 2 + npb),
            cache_to_blocks(cfg, slot_cache, blk), 1,
            list(range(2, 2 + npb)))
        self._decode_pair(cfg, params, cont, paged, rows=(1,))

    @pytest.mark.parametrize("blk", [2, 4])
    def test_windowed_ring_wraparound(self, blk):
        """Decode past the SWA ring extent: the write slot wraps back to
        block 0 of the slot's table and stays bit-identical."""
        cfg, params = _setup("h2o-danube-1.8b")
        sb = kv_buf_len(cfg, 24)
        npb = paged_slot_blocks(cfg, 24, blk)
        slot_cache, _ = prefill(cfg, params, _tokens(cfg, 1, 6, key=8),
                                cache_len=24)
        cont = _install_contiguous(init_cache(cfg, 2, 24), slot_cache, 1)
        paged = _install_paged(
            init_paged_cache(cfg, 2, 24, blk, 2 + npb),
            cache_to_blocks(cfg, slot_cache, blk), 1,
            list(range(2, 2 + npb)))
        cont, paged = self._decode_pair(cfg, params, cont, paged,
                                        rows=(1,), steps=sb)
        assert int(cont["pos"][1]) > sb      # the ring actually wrapped

    def test_shared_prefix_aliasing(self):
        """Two rows whose tables alias the same (read-only) prefix block
        but own private tails decode bit-identically to two full
        contiguous copies — the COW invariant of the prefix cache."""
        cfg, params = _setup("smollm-360m")
        blk, max_seq = 4, 16
        npb = paged_slot_blocks(cfg, max_seq, blk)
        slot_cache, _ = prefill(cfg, params, _tokens(cfg, 1, 6, key=9),
                                cache_len=max_seq)
        blocks = cache_to_blocks(cfg, slot_cache, blk)
        cont = init_cache(cfg, 3, max_seq)
        cont = _install_contiguous(cont, slot_cache, 1)
        cont = _install_contiguous(cont, slot_cache, 2)
        # block 3 holds positions [0, 4): shared; tails 4.. are private
        paged = init_paged_cache(cfg, 3, max_seq, blk, 3 + 2 * npb - 1)
        paged = _install_paged(paged, blocks, 1,
                               [3] + list(range(4, 3 + npb)))
        paged = _install_paged(paged, blocks, 2,
                               [3] + list(range(3 + npb, 2 + 2 * npb)))
        # feed *different* tokens per row so the rows diverge while the
        # shared block keeps being read by both
        toks = jnp.zeros((3,), jnp.int32)
        for t in range(5):
            cont, la = decode_step(cfg, params, cont, toks)
            paged, lb = decode_step(cfg, params, paged, toks)
            np.testing.assert_array_equal(np.asarray(la[1:]),
                                          np.asarray(lb[1:]),
                                          err_msg=f"step {t}")
            nxt = np.zeros((3,), np.int32)
            nxt[1] = int(jnp.argmax(la[1]))
            nxt[2] = int(jnp.argmin(la[2])) % cfg.vocab_size
            toks = jnp.asarray(nxt)
        # the shared prefix block was never written by either row
        np.testing.assert_array_equal(np.asarray(paged["kp"][:, 3]),
                                      np.asarray(blocks[0][:, 0]))


class TestBlockPool:
    """Host-side pool allocator: no double-free, no aliasing, and
    free + live == n_blocks − reserved under arbitrary op sequences."""

    def test_double_free_raises(self):
        pool = BlockPool(8, reserved=2)
        bids = pool.alloc(3)
        pool.release(bids)
        with pytest.raises(ValueError):
            pool.release(bids)
        pool.check_conservation()

    def test_alloc_never_returns_reserved_or_live(self):
        pool = BlockPool(10, reserved=3)
        a = pool.alloc(4)
        b = pool.alloc(3)
        assert not set(a) & set(b)
        assert all(bid >= 3 for bid in a + b)
        with pytest.raises(MemoryError):
            pool.alloc(1)           # 7 usable, 7 live
        pool.check_conservation()

    def test_eviction_under_pressure(self):
        """Allocation pressure evicts LRU cache entries (entry refs
        only — request-held blocks always survive) before failing."""
        pool = BlockPool(10, reserved=2)
        a = pool.alloc(4)
        pool.cache_insert(b"p1", a[:2])
        pool.release(a)             # entry still pins a[:2]
        assert pool.free_blocks == 6 and pool.cached_entries == 1
        held = pool.alloc(2)        # no pressure: entry survives
        assert pool.cached_entries == 1
        big = pool.alloc(6)         # needs the pinned pair -> evict
        assert pool.evictions == 1 and pool.cached_entries == 0
        assert len(big) == 6 and not set(big) & set(held)
        pool.check_conservation()
        with pytest.raises(MemoryError):
            pool.alloc(1)           # held blocks were NOT reclaimed
        pool.release(held + big)
        pool.check_conservation()

    def test_lookup_retains_and_refreshes_lru(self):
        pool = BlockPool(12, reserved=0)
        a, b = pool.alloc(2), pool.alloc(2)
        pool.cache_insert(b"a", a)
        pool.cache_insert(b"b", b)
        pool.release(a)
        pool.release(b)
        got = pool.cache_lookup(b"a")       # refreshes "a"; caller ref
        assert got == a
        pool.alloc(10)                      # pressure evicts "b" first
        assert pool.cache_lookup(b"b") is None
        assert pool.cache_lookup(b"a") == a     # still resident (held)
        pool.check_conservation()

    @settings(max_examples=25, deadline=None)
    @given(ops=st.lists(
        st.tuples(st.sampled_from(["alloc", "release", "insert", "lookup"]),
                  st.integers(0, 7)), max_size=40))
    def test_random_op_sequences_conserve(self, ops):
        pool = BlockPool(16, reserved=3)
        held = []                   # groups we hold a ref on
        keys = []
        for step, (op, k) in enumerate(ops):
            if op == "alloc":
                try:
                    bids = pool.alloc(k % 5 + 1)
                except MemoryError:
                    pool.check_conservation()
                    continue
                # freshly allocated blocks alias nothing we hold
                flat = {b for grp in held for b in grp}
                assert not set(bids) & flat
                assert all(b >= 3 for b in bids)
                held.append(bids)
            elif op == "release" and held:
                pool.release(held.pop(k % len(held)))
            elif op == "insert" and held:
                key = f"k{step}".encode()
                pool.cache_insert(key, held[k % len(held)])
                keys.append(key)
            elif op == "lookup" and keys:
                got = pool.cache_lookup(keys[k % len(keys)])
                if got is not None:
                    held.append(got)    # lookup retains for the caller
            pool.check_conservation()
        for grp in held:
            pool.release(grp)
        pool.check_conservation()


class TestPagedServer:
    """End-to-end: the paged scheduler is token-identical to the
    contiguous one, prefix hits fire on shared prompts, and retire
    reclaims blocks at every phase (the mid-prefill cancel bugfix)."""

    def _params(self, mesh):
        from repro.dist.sharding import param_pspecs, to_shardings
        cfg = get_config("smollm-360m").reduced()
        shape = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))
        psh = to_shardings(mesh, param_pspecs(cfg, mesh, shape))
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=psh)(jax.random.PRNGKey(0))
        return cfg, params

    def _server(self, cfg, params, mesh, paged, **kw):
        srv = dict(max_batch=2, max_seq=32, max_new_tokens=4,
                   prefill_chunk=4)
        srv.update(kw)
        return Server(cfg, params, mesh, srv=ServerConfig(
            paged=paged, block_size=4, **srv))

    def _prompts(self, cfg, n=5, shared=8, tail=4):
        rng = np.random.default_rng(2)
        prefix = rng.integers(0, cfg.vocab_size, size=shared)
        return [np.concatenate([prefix,
                                rng.integers(0, cfg.vocab_size, size=tail)])
                for _ in range(n)]

    def test_paged_tokens_equal_contiguous_with_prefix_hits(self, mesh22):
        cfg, params = self._params(mesh22)
        outs = {}
        servers = {}
        for paged in (False, True):
            s = self._server(cfg, params, mesh22, paged)
            for pr in self._prompts(cfg):
                s.submit(pr)
            s.run()
            outs[paged] = {r.rid: r.out_tokens for r in s.done}
            servers[paged] = s
        assert outs[True] == outs[False]
        assert servers[True].prefix_hits > 0
        servers[True].pool.check_conservation()
        st_ = servers[True].stats()
        assert st_["prefix_hits"] == servers[True].prefix_hits
        assert st_["pool_free_blocks"] == servers[True].pool.free_blocks

    def test_prefix_cache_off_still_identical(self, mesh22):
        cfg, params = self._params(mesh22)
        ref = self._server(cfg, params, mesh22, False)
        s = self._server(cfg, params, mesh22, True, prefix_cache=False)
        for pr in self._prompts(cfg, n=3):
            ref.submit(pr)
            s.submit(pr)
        ref.run()
        s.run()
        assert ({r.rid: r.out_tokens for r in s.done}
                == {r.rid: r.out_tokens for r in ref.done})
        assert s.prefix_hits == 0
        s.pool.check_conservation()

    def test_cancel_mid_prefill_reclaims_blocks(self, mesh22):
        """Regression: a cancel while phase == 'prefill' must release the
        admission scratch *and* the slot's pool blocks, and the slot must
        be reusable afterwards."""
        cfg, params = self._params(mesh22)
        s = self._server(cfg, params, mesh22, True, max_batch=1,
                         max_new_tokens=2)
        rng = np.random.default_rng(3)
        rid = s.submit(rng.integers(0, cfg.vocab_size, size=12))
        s.step()                      # admit + first prefill chunk only
        req = s.slots[0]
        assert req is not None and req.phase == "prefill"
        assert req._scratch is not None and req._blocks
        free_before_cancel = s.pool.free_blocks
        assert s.cancel(rid)
        assert req._scratch is None and req._blocks == []
        assert s.slots[0] is None
        assert s.pool.free_blocks > free_before_cancel
        s.pool.check_conservation()
        full = s.pool.free_blocks
        # the parked slot admits and completes a fresh request
        rid2 = s.submit(rng.integers(0, cfg.vocab_size, size=6))
        s.run()
        done = {r.rid: r for r in s.done}
        assert done[rid].cancelled and done[rid].out_tokens == []
        assert len(done[rid2].out_tokens) == 2
        # entries published by rid2's prompt may pin blocks; evict them
        while s.pool.cached_entries:
            s.pool._evict_lru()
        assert s.pool.free_blocks == full
        s.pool.check_conservation()

    def test_cancel_queued_and_unknown(self, mesh22):
        cfg, params = self._params(mesh22)
        s = self._server(cfg, params, mesh22, True)
        rid = s.submit(np.asarray([1, 2, 3], np.int32))
        assert s.cancel(rid)          # still queued: dropped without slot
        assert not s.cancel(rid)      # already gone
        assert s.done[0].cancelled and s.done[0].out_tokens == []
