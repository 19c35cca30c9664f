"""Per-kernel shape/dtype sweeps, asserted allclose against ref.py oracles
(interpret mode executes the Pallas body on CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.kernels import common
from repro.kernels.cc_matmul import (
    allgather_matmul_pallas,
    allgather_matmul_ref,
    matmul_reducescatter_pallas,
    matmul_reducescatter_ref,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.ssd import ssd, ssd_chunk_fed
from repro.kernels.ssd.ref import ssd_ref


class TestMatmulKernel:
    @pytest.mark.parametrize("m,k,n", [
        (128, 128, 128), (100, 200, 150), (256, 64, 512), (1, 7, 3),
        (384, 128, 128),
    ])
    def test_shapes(self, m, k, n):
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
        np.testing.assert_allclose(np.asarray(matmul(x, w)),
                                   np.asarray(matmul_ref(x, w)),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("act", ["none", "relu", "relu2", "silu", "gelu"])
    def test_fused_activations(self, act):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 96), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (96, 80), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(2), (80,), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(matmul(x, w, b, activation=act)),
            np.asarray(matmul_ref(x, w, b, activation=act)),
            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 64)).astype(dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 64)).astype(dtype)
        got = matmul(x, w, out_dtype=jnp.float32)
        want = matmul_ref(x, w, out_dtype=jnp.float32)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol)

    def test_batched(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 40, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        np.testing.assert_allclose(np.asarray(matmul(x, w)),
                                   np.asarray(matmul_ref(x, w)),
                                   rtol=2e-5, atol=2e-5)

    @given(m=st.integers(1, 300), k=st.integers(1, 260), n=st.integers(1, 200))
    @settings(max_examples=8, deadline=None)
    def test_padding_is_exact(self, m, k, n):
        """Zero-padding to block multiples must not perturb results."""
        x = jax.random.normal(jax.random.PRNGKey(m), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(n), (k, n), jnp.float32)
        np.testing.assert_allclose(np.asarray(matmul(x, w)),
                                   np.asarray(matmul_ref(x, w)),
                                   rtol=3e-5, atol=3e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("sq,skv", [(128, 128), (100, 100), (64, 256),
                                        (256, 256)])
    def test_causal_shapes(self, sq, skv):
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, sq, 32))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 2, skv, 32))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 2, skv, 32))
        got = flash_attention(q, k, v, causal=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window", [16, 64, 129])
    def test_sliding_window(self, window):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 200, 32))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 200, 32))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 200, 32))
        got = flash_attention(q, k, v, causal=True, window=window)
        want = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_non_causal(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 128, 32))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 128, 32))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 128, 32))
        got = flash_attention(q, k, v, causal=False)
        want = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("group", [1, 2, 4])
    def test_gqa_groups(self, group):
        hkv = 2
        q = jax.random.normal(jax.random.PRNGKey(0),
                              (1, hkv * group, 128, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, hkv, 128, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, hkv, 128, 16))
        got = flash_attention(q, k, v, causal=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        q = jax.random.normal(jax.random.PRNGKey(0),
                              (1, 2, 128, 32)).astype(jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1),
                              (1, 2, 128, 32)).astype(jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2),
                              (1, 2, 128, 32)).astype(jnp.bfloat16)
        got = flash_attention(q, k, v, causal=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2, atol=3e-2)

    @pytest.mark.parametrize("causal,window,sq", [(True, None, 100),
                                                   (True, 24, 72),
                                                   (False, None, 128)])
    def test_grads_match_blockwise(self, causal, window, sq):
        """The custom VJP: grads of the Pallas forward are those of
        layers.blockwise_attention on the same inputs."""
        from repro.models.layers import blockwise_attention

        q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, sq, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 2, sq, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 2, sq, 16))
        ct = jax.random.normal(jax.random.PRNGKey(3), (2, 4, sq, 16))

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v, causal=causal, window=window) * ct)

        got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(blockwise_attention),
                        argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)


class TestSSD:
    @pytest.mark.parametrize("s,chunk", [(64, 16), (50, 16), (128, 32),
                                         (17, 8)])
    def test_shapes_vs_ref(self, s, chunk):
        B, H, P, G, N = 2, 4, 16, 2, 8
        xs = jax.random.normal(jax.random.PRNGKey(0), (B, s, H, P))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                               (B, s, H)))
        a = -jnp.exp(jnp.linspace(0.0, 1.0, H))
        bm = jax.random.normal(jax.random.PRNGKey(2), (B, s, G, N))
        cm = jax.random.normal(jax.random.PRNGKey(3), (B, s, G, N))
        d = jnp.ones((H,))
        y, st_ = ssd(xs, dt, a, bm, cm, d, chunk=chunk)
        yr, sr = ssd_ref(xs, dt, a, bm, cm, d)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(st_), np.asarray(sr),
                                   rtol=2e-3, atol=2e-3)

    def test_decode_step_consistency(self):
        """Sequential decode steps == full-sequence SSD."""
        from repro.kernels.ssd.ref import ssd_decode_step

        B, s, H, P, G, N = 1, 12, 2, 8, 1, 4
        xs = jax.random.normal(jax.random.PRNGKey(0), (B, s, H, P))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                               (B, s, H)))
        a = -jnp.exp(jnp.linspace(0.0, 0.5, H))
        bm = jax.random.normal(jax.random.PRNGKey(2), (B, s, G, N))
        cm = jax.random.normal(jax.random.PRNGKey(3), (B, s, G, N))
        d = jnp.zeros((H,))
        y_full, state_full = ssd(xs, dt, a, bm, cm, d, chunk=4)

        state = jnp.zeros((B, H, N, P))
        ys = []
        for t in range(s):
            state, y = ssd_decode_step(state, xs[:, t], dt[:, t], a,
                                       bm[:, t], cm[:, t], d)
            ys.append(y)
        y_seq = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_full),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(state),
                                   np.asarray(state_full),
                                   rtol=2e-3, atol=2e-3)


class TestCommonInterpret:
    """kernels/common.py: the one shared interpret-mode policy."""

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv(common.INTERPRET_ENV, "1")
        assert common.should_interpret() is True
        monkeypatch.setenv(common.INTERPRET_ENV, "0")
        assert common.should_interpret() is False
        assert common.supports_remote_dma() is False  # forced interpret

    def test_default_follows_backend(self, monkeypatch):
        monkeypatch.delenv(common.INTERPRET_ENV, raising=False)
        expect = jax.default_backend() == "cpu"
        assert common.should_interpret() is expect

    def test_legacy_alias_survives(self):
        """matmul/ops kept its historical private name as an alias."""
        from repro.kernels.matmul import ops as matmul_ops

        assert matmul_ops._should_interpret is common.should_interpret


def _ring_mesh(n):
    import numpy as _np

    return jax.sharding.Mesh(_np.array(jax.devices()[:n]), ("x",))


def _run_sharded(mesh, fn, args, in_specs, out_spec):
    f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                              out_specs=out_spec, check_vma=False))
    return np.asarray(f(*args))


class TestCCMatmulAllGather:
    """Fused AG·matmul: allclose vs the lax oracle, bitwise vs overlap.py."""

    @pytest.mark.parametrize("bidir", [False, True])
    @pytest.mark.parametrize("n_ranks", [2, 3, 4])
    @pytest.mark.parametrize("b_loc,k,m", [(8, 16, 32), (6, 24, 40)])
    def test_vs_ref_and_overlap(self, n_ranks, bidir, b_loc, k, m):
        from repro.core import overlap

        mesh = _ring_mesh(n_ranks)
        x = jax.random.normal(jax.random.PRNGKey(0), (n_ranks * b_loc, k))
        w = jax.random.normal(jax.random.PRNGKey(1), (k, m))
        args = (x, w)
        specs = (P("x", None), P(None, None))
        fused = _run_sharded(
            mesh,
            functools.partial(allgather_matmul_pallas, axis="x",
                              bidirectional=bidir),
            args, specs, P(None, None))
        ref = _run_sharded(
            mesh,
            functools.partial(allgather_matmul_ref, axis="x"),
            args, specs, P(None, None))
        streamed = _run_sharded(
            mesh,
            functools.partial(overlap.allgather_matmul, axis="x",
                              bidirectional=bidir),
            args, specs, P(None, None))
        np.testing.assert_allclose(fused, ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(
            fused, streamed,
            err_msg="fused AG schedule must be bit-identical to overlap.py")

    def test_batched_3d(self):
        mesh = _ring_mesh(4)
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 4 * 8, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
        fused = _run_sharded(
            mesh, functools.partial(allgather_matmul_pallas, axis="x"),
            (x, w), (P(None, "x", None), P(None, None)), P(None, None, None))
        want = np.einsum("bik,kn->bin", np.asarray(x), np.asarray(w))
        np.testing.assert_allclose(fused, want, rtol=1e-4, atol=1e-4)

    def test_grads_match_ref(self):
        mesh = _ring_mesh(4)
        x = jax.random.normal(jax.random.PRNGKey(0), (4 * 8, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 32))

        def loss(fn):
            def inner(x_, w_):
                return jnp.sum(fn(x_, w_) ** 2)

            g = jax.jit(jax.shard_map(
                jax.grad(inner, argnums=(0, 1)), mesh=mesh,
                in_specs=(P("x", None), P(None, None)),
                out_specs=(P("x", None), P(None, None)), check_vma=False))
            return g(x, w)

        gx_f, gw_f = loss(functools.partial(
            allgather_matmul_pallas, axis="x"))
        gx_r, gw_r = loss(functools.partial(
            allgather_matmul_ref, axis="x"))
        np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                                   rtol=1e-4, atol=1e-4)


class TestCCMatmulReduceScatter:
    """Fused matmul·RS: allclose vs the lax oracle, bitwise vs overlap.py."""

    @pytest.mark.parametrize("bidir", [False, True])
    @pytest.mark.parametrize("n_ranks", [2, 3, 4])
    @pytest.mark.parametrize("b_loc,k,m", [(8, 16, 32), (6, 24, 40)])
    def test_vs_ref_and_overlap(self, n_ranks, bidir, b_loc, k, m):
        from repro.core import overlap

        mesh = _ring_mesh(n_ranks)
        rows = n_ranks * n_ranks * b_loc       # local rows divisible by n
        x = jax.random.normal(jax.random.PRNGKey(2), (rows, k))
        w = jax.random.normal(jax.random.PRNGKey(3), (k, m))
        args = (x, w)
        specs = (P("x", None), P(None, None))
        fused = _run_sharded(
            mesh,
            functools.partial(matmul_reducescatter_pallas, axis="x",
                              bidirectional=bidir),
            args, specs, P("x", None))
        ref = _run_sharded(
            mesh,
            functools.partial(matmul_reducescatter_ref, axis="x"),
            args, specs, P("x", None))
        streamed = _run_sharded(
            mesh,
            functools.partial(overlap.matmul_reducescatter, axis="x",
                              bidirectional=bidir),
            args, specs, P("x", None))
        np.testing.assert_allclose(fused, ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(
            fused, streamed,
            err_msg="fused RS schedule must be bit-identical to overlap.py")

    def test_grads_match_ref(self):
        mesh = _ring_mesh(4)
        x = jax.random.normal(jax.random.PRNGKey(2), (4 * 16, 8))
        w = jax.random.normal(jax.random.PRNGKey(3), (8, 12))

        def loss(fn):
            def inner(x_, w_):
                return jnp.sum(fn(x_, w_) ** 2)

            g = jax.jit(jax.shard_map(
                jax.grad(inner, argnums=(0, 1)), mesh=mesh,
                in_specs=(P("x", None), P(None, None)),
                out_specs=(P("x", None), P(None, None)), check_vma=False))
            return g(x, w)

        gx_f, gw_f = loss(functools.partial(
            matmul_reducescatter_pallas, axis="x"))
        gx_r, gw_r = loss(functools.partial(
            matmul_reducescatter_ref, axis="x"))
        np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                                   rtol=1e-4, atol=1e-4)


class TestSSDChunkFed:
    """Chunk-fed SSD scan: segments streamed in, state carried across."""

    def _inputs(self, s):
        B, H, P_, G, N = 2, 4, 16, 2, 8
        xs = jax.random.normal(jax.random.PRNGKey(0), (B, s, H, P_))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                               (B, s, H)))
        a = -jnp.exp(jnp.linspace(0.0, 1.0, H))
        bm = jax.random.normal(jax.random.PRNGKey(2), (B, s, G, N))
        cm = jax.random.normal(jax.random.PRNGKey(3), (B, s, G, N))
        d = jnp.ones((H,))
        return xs, dt, a, bm, cm, d

    def test_aligned_segments_bitwise(self):
        """Chunk-aligned segment cuts reproduce the bulk scan exactly."""
        xs, dt, a, bm, cm, d = self._inputs(64)
        y0, st0 = ssd(xs, dt, a, bm, cm, d, chunk=16)
        cuts = [(0, 16), (16, 48), (48, 64)]

        def fetch(k):
            lo, hi = cuts[k]
            return xs[:, lo:hi], dt[:, lo:hi], bm[:, lo:hi], cm[:, lo:hi]

        y1, st1 = ssd_chunk_fed(fetch, len(cuts), a, d, chunk=16)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
        np.testing.assert_array_equal(np.asarray(st0), np.asarray(st1))

    def test_unaligned_segments_allclose(self):
        """Unaligned cuts move chunk boundaries: allclose, state exact-ish."""
        xs, dt, a, bm, cm, d = self._inputs(50)
        y0, st0 = ssd(xs, dt, a, bm, cm, d, chunk=16)
        cuts = [(0, 20), (20, 50)]

        def fetch(k):
            lo, hi = cuts[k]
            return xs[:, lo:hi], dt[:, lo:hi], bm[:, lo:hi], cm[:, lo:hi]

        y1, st1 = ssd_chunk_fed(fetch, len(cuts), a, d, chunk=16)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(st0), np.asarray(st1),
                                   rtol=2e-3, atol=2e-3)

    def test_init_state_resumes_scan(self):
        """Seeding init_state continues a previous scan exactly."""
        xs, dt, a, bm, cm, d = self._inputs(32)
        y0, st0 = ssd(xs, dt, a, bm, cm, d, chunk=8)
        _, st_head = ssd(xs[:, :16], dt[:, :16], a, bm[:, :16], cm[:, :16],
                         d, chunk=8)
        y_tail, st_tail = ssd(xs[:, 16:], dt[:, 16:], a, bm[:, 16:],
                              cm[:, 16:], d, chunk=8, init_state=st_head)
        np.testing.assert_array_equal(np.asarray(y0[:, 16:]),
                                      np.asarray(y_tail))
        np.testing.assert_array_equal(np.asarray(st0), np.asarray(st_tail))

    def test_layers_binding_bitwise(self):
        """cfg.ssm_stream_segments routes the mamba block through the
        chunk-fed scan, bit-identical to the bulk path."""
        import dataclasses

        from repro.configs import get_config
        from repro.models import layers as L
        from repro.models.model import init_params

        cfg = get_config("mamba2-2.7b").reduced()
        cfg = dataclasses.replace(cfg, attn_impl="pallas")
        params = init_params(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda p: p[0], params["layers"])
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (2, 4 * cfg.ssm_chunk + 3, cfg.d_model))
        bulk = L.mamba2_block(cfg, lp["mamba"], x)
        fed = L.mamba2_block(
            dataclasses.replace(cfg, ssm_stream_segments=3),
            lp["mamba"], x)
        np.testing.assert_array_equal(np.asarray(bulk), np.asarray(fed))
