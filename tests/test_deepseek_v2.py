"""DeepSeek-V2-Lite's mechanisms in the program: the YaRN rope and
softmax scale, the grouped-matmul kernel against its oracle, dropless
routing over a held share of the experts, and the named scopes the
profiler shows."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.grouped_matmul import gmm_ref, moe_gmm
from repro.models import layers as L
from repro.models.decode import decode_step, init_cache
from repro.models.model import init_params
from repro.models.prefill import prefill

CFG = get_config("deepseek-v2-lite")
SCOPES = ("moe.route", "moe.experts", "moe.combine", "mla.absorb",
          "mla.attend")


def test_yarn_range_and_softmax_scale_pinned():
    assert L.yarn_range(CFG.qk_rope_dim, CFG.rope_theta,
                        CFG.rope_scaling) == (10, 23)
    # 192^-1/2 · (0.1 · 0.707 · ln 40 + 1)^2
    assert L.mla_softmax_scale(CFG) == pytest.approx(0.114721, abs=1e-6)


def test_yarn_inv_freq_pinned():
    inv = np.asarray(L.rope_freqs(64, 1e4, CFG.rope_scaling), np.float64)
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0.0, 1.0)
    np.testing.assert_allclose(inv, extra / 40 * ramp + extra * (1 - ramp),
                               rtol=2e-6)
    np.testing.assert_allclose(inv[:10], extra[:10], rtol=2e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=2e-6)


def test_rope_rotates_interleaved_pairs():
    """Pair (2i, 2i+1) turns by frequency i; the result is laid out evens
    then odds, in the query and the key alike."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
    pos = jnp.arange(3)
    got = np.asarray(L.apply_rope(x, pos, 1e4, interleave=True))
    f = np.asarray(L.rope_freqs(8, 1e4))
    ang = np.arange(3)[:, None] * f
    xe, xo = np.asarray(x)[:, 0::2], np.asarray(x)[:, 1::2]
    want = np.concatenate([xe * np.cos(ang) - xo * np.sin(ang),
                           xo * np.cos(ang) + xe * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("sizes", [
    (32, 0, 16, 48, 0),          # uneven groups, empty ones between
    (0, 0, 0, 0, 0),             # no row routed here
    (0, 64, 16, 0, 80),          # leading empty group, full extent
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_matches_ref(sizes, dtype):
    tm, m, k, n = 16, 160, 24, 40
    lhs = jax.random.normal(jax.random.PRNGKey(1), (m, k)).astype(dtype)
    rhs = jax.random.normal(jax.random.PRNGKey(2), (5, k, n)).astype(dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    used = int(sum(sizes))
    got = moe_gmm(lhs, rhs, gs, tile_m=tm, interpret=True)
    want = gmm_ref(lhs, rhs, gs)
    assert got.shape == (m, n) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got[:used], np.float32),
                               np.asarray(want[:used], np.float32),
                               rtol=tol, atol=tol)
    assert not np.asarray(want[used:], np.float32).any()


def _tiny():
    cfg = CFG.reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_reduced_config_keeps_the_share():
    cfg = CFG.reduced()
    assert cfg.first_dense_layers == 1 and cfg.n_moe_layers >= 2
    assert (cfg.n_experts, cfg.n_local_experts, cfg.experts_per_token) == (
        8, 4, 2)
    assert cfg.capacity_factor is None and cfg.q_lora_rank == 0
    assert cfg.dense_d_ff != cfg.d_ff


def test_dropless_equals_a_loop_over_held_experts():
    """Prefill's sorted grouped matmul and decode's dense combine both
    give, per token, the held experts' outputs weighted by the un-
    renormalised softmax, plus the shared experts."""
    cfg, params = _tiny()
    mp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 7, cfg.d_model))
    probs = jax.nn.softmax(x @ mp["router"], -1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    want = L.mlp(cfg, mp["shared"], x)
    for e in range(cfg.n_local_experts):
        ge = jnp.sum(jnp.where(idx == cfg.expert_offset + e, w, 0.0), -1)
        ep = {k: mp[k][e] for k in ("w_up", "w_gate", "w_down")}
        want = want + ge[..., None] * L.mlp(cfg, ep, x)
    for dense in (False, True):
        got = L.moe(cfg, mp, x, dense_combine=dense)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_offset_share_routes_to_its_own_experts():
    cfg, params = _tiny()
    mp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 9, cfg.d_model))
    a = L.moe(cfg, mp, x)
    b = L.moe(dataclasses.replace(cfg, expert_offset=4), mp, x)
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_named_scopes_in_prefill_and_decode():
    cfg, params = _tiny()
    toks = jnp.zeros((1, 16), jnp.int32)
    pre = jax.jit(lambda p, t: prefill(cfg, p, t, cache_len=32)).lower(
        params, toks).as_text(debug_info=True)
    cache = init_cache(cfg, 2, 32)
    dec = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t)).lower(
        params, cache, jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    for text in (pre, dec):
        assert [s for s in SCOPES if s not in text] == []
