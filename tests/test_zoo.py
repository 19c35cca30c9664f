"""Cross-arch serving conformance: the chunk-carry contract (PR 8).

Every arch in the registry streams its prefill — the carry is typed per
family (ring K/V rows, MLA latent rows, constant-size SSD state, the
hybrid pair, encoder-once + decoder chunks) — and chunked ≡ bulk is
asserted *bitwise* at the model layer and token-exact end-to-end on a
real :class:`Server`, for every ``get_config`` name.  MoE rides the ring
carry under the chunk-local capacity bound
(:func:`repro.models.prefill.moe_chunk_agree_mask`): exact when no row
overflows either program (the identity runs assert it at
``capacity_factor >= n_experts``), and the bound's contrapositive is
asserted too — a tight capacity makes the keep decisions disagree and
the mask names the rows.

Also here: the ``prefill_chunk_cuts`` tiling property (both spellings,
carry multiples, ragged tails), and the no-silent-fallback regression —
requesting chunked admission on an arch the gate rejects warns once at
build time with the reason and surfaces ``bulk`` in ``stats()``.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import chunk_carry_spec, serving_features
from repro.models.decode import decode_step, supports_paged
from repro.models.model import init_params
from repro.models.prefill import (
    chunk_support,
    init_prefill_scratch,
    moe_chunk_agree_mask,
    prefill,
    prefill_chunk_cuts,
    prefill_chunked,
)
from repro.runtime.server import Server, ServerConfig

@pytest.fixture(scope="module", autouse=True)
def _fresh_jax_caches():
    """The zoo sweep compiles every arch's programs on top of whatever
    the rest of the suite already compiled in this process; dropping the
    accumulated executables first keeps the long single-process tier-1
    run stable (observed XLA CPU segfaults in backend_compile without
    this, never when the module runs alone)."""
    import gc

    jax.clear_caches()
    gc.collect()
    yield
    _PARAMS.clear()
    jax.clear_caches()
    gc.collect()


ZOO = list(ARCH_NAMES)
MOE_ARCHS = tuple(n for n in ZOO if get_config(n).family == "moe")
STATE_ARCHS = tuple(n for n in ZOO
                    if chunk_carry_spec(get_config(n).reduced()).kind
                    in ("state", "hybrid"))
PAGED_ARCHS = tuple(n for n in ZOO
                    if supports_paged(get_config(n).reduced()))

#: MoE identity runs pin capacity_factor >= n_experts so no row overflows
#: in either the bulk or the chunk-local program — the exactness condition
#: of moe_chunk_agree_mask's bound.  Dropless MoE (capacity_factor None)
#: drops nothing and runs as it is.
_NO_OVERFLOW = {"capacity_factor": 8.0}

_PARAMS = {}


def _zoo_cfg(arch):
    cfg = get_config(arch).reduced()
    if arch in MOE_ARCHS and cfg.capacity_factor is not None:
        cfg = dataclasses.replace(cfg, **_NO_OVERFLOW)
    return cfg


def _setup(arch):
    """(cfg, params), cached module-wide — the zoo sweep re-enters per
    test and param init dominates otherwise."""
    if arch not in _PARAMS:
        cfg = _zoo_cfg(arch)
        _PARAMS[arch] = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return _PARAMS[arch]


def _tokens(cfg, b, s, key=1):
    return jax.random.randint(jax.random.PRNGKey(key), (b, s), 0,
                              cfg.vocab_size)


def _frontend(cfg, b=None, key=2):
    if not cfg.frontend:
        return None
    shape = (cfg.frontend_tokens, cfg.frontend_dim)
    if b is not None:
        shape = (b,) + shape
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _assert_tree_equal(a, b, msg=""):
    assert set(a) == set(b), f"{msg}: leaf sets differ"
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"{msg} leaf {k!r}")


_MESH = None


def _mesh():
    global _MESH
    if _MESH is None:
        from repro.launch.mesh import make_host_mesh
        _MESH = make_host_mesh(1, 1)
    return _MESH


def _server_params(arch):
    """Params jitted onto the serving mesh (cached)."""
    key = (arch, "srv")
    if key not in _PARAMS:
        from repro.dist.sharding import param_pspecs, to_shardings
        cfg = _zoo_cfg(arch)
        mesh = _mesh()
        shape = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))
        psh = to_shardings(mesh, param_pspecs(cfg, mesh, shape))
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=psh)(jax.random.PRNGKey(0))
        _PARAMS[key] = (cfg, params)
    return _PARAMS[key]


class TestZooModelConformance:
    """prefill_chunked ≡ prefill, bit for bit, for every registry arch —
    cache leaves, logits, and the decode step that follows."""

    @pytest.mark.parametrize("arch", ZOO)
    def test_chunked_bit_identical_and_split_invariant(self, arch):
        cfg, params = _setup(arch)
        assert chunk_support(cfg)[0], chunk_support(cfg)[1]
        b, s = 2, 13
        toks = _tokens(cfg, b, s)
        fe = _frontend(cfg, b)
        cl = 32
        bulk_cache, bulk_logits = prefill(cfg, params, toks, fe,
                                          cache_len=cl)
        for kw in ({"n_chunks": 2}, {"n_chunks": 3}, {"chunk_len": 5}):
            cache, logits = prefill_chunked(cfg, params, toks, fe,
                                            cache_len=cl, **kw)
            _assert_tree_equal(bulk_cache, cache, f"{arch} {kw}")
            np.testing.assert_array_equal(np.asarray(bulk_logits),
                                          np.asarray(logits),
                                          err_msg=f"{arch} {kw}")

    @pytest.mark.parametrize("arch", ZOO)
    def test_decode_continues_identically(self, arch):
        cfg, params = _setup(arch)
        toks = _tokens(cfg, 1, 9)
        fe = _frontend(cfg, 1)
        ca, la = prefill(cfg, params, toks, fe, cache_len=16)
        cb, lb = prefill_chunked(cfg, params, toks, fe, cache_len=16,
                                 n_chunks=3)
        nxt = jnp.argmax(la, -1).astype(jnp.int32)
        ca, la2 = decode_step(cfg, params, ca, nxt)
        cb, lb2 = decode_step(cfg, params, cb, nxt)
        np.testing.assert_array_equal(np.asarray(la2), np.asarray(lb2),
                                      err_msg=arch)

    @pytest.mark.parametrize("arch", STATE_ARCHS)
    def test_ssm_carry_is_constant_size(self, arch):
        """The streamed-SSM selling point: the carry (SSD state + conv
        tail) does not grow with the prompt."""
        cfg = _zoo_cfg(arch)
        short = jax.eval_shape(lambda: init_prefill_scratch(cfg, 1, 16))
        long = jax.eval_shape(lambda: init_prefill_scratch(cfg, 1, 256))
        for k in ("ssm_state", "conv_state"):
            assert short[k].shape == long[k].shape, (arch, k)

    @settings(max_examples=6, deadline=None)
    @given(s=st.integers(2, 24), n=st.integers(2, 6),
           arch=st.sampled_from(("mamba2-2.7b", "h2o-danube-1.8b",
                                 "whisper-tiny")))
    def test_drawn_lengths_and_cuts(self, s, n, arch):
        """Hypothesis sweep over the tricky carries: SSD multiple
        snapping (mamba2), SWA ring wraparound (danube window < s), the
        capped whisper decoder."""
        cfg, params = _setup(arch)
        if cfg.family == "encdec":
            s = min(s, cfg.decoder_max_seq)
        toks = _tokens(cfg, 1, s, key=50 + s)
        fe = _frontend(cfg, 1)
        ca, la = prefill(cfg, params, toks, fe, cache_len=s)
        cb, lb = prefill_chunked(cfg, params, toks, fe, cache_len=s,
                                 n_chunks=n)
        _assert_tree_equal(ca, cb, f"{arch} s={s} n={n}")
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestChunkCutsProperty:
    """prefill_chunk_cuts tiles [0, s_total) exactly once — both
    spellings, ragged tails, carry multiples, s_total < chunk_len."""

    @staticmethod
    def _assert_tiling(cuts, s, multiple):
        assert cuts[0][0] == 0 and cuts[-1][1] == s
        assert all(a[1] == b[0] for a, b in zip(cuts, cuts[1:]))
        assert all(lo < hi for lo, hi in cuts)
        covered = [p for lo, hi in cuts for p in range(lo, hi)]
        assert covered == list(range(s))
        # every interior boundary lands on the carry multiple
        for _, hi in cuts[:-1]:
            assert hi % multiple == 0

    @settings(max_examples=40, deadline=None)
    @given(s=st.integers(1, 64), c=st.integers(1, 80),
           m=st.sampled_from((1, 4, 8)))
    def test_chunk_len_spelling(self, s, c, m):
        cuts = prefill_chunk_cuts(s, chunk_len=c, multiple=m)
        self._assert_tiling(cuts, s, m)
        if c >= s:
            assert cuts == [(0, s)]

    @settings(max_examples=40, deadline=None)
    @given(s=st.integers(1, 64), n=st.integers(1, 9),
           m=st.sampled_from((1, 4, 8)))
    def test_n_chunks_spelling(self, s, n, m):
        cuts = prefill_chunk_cuts(s, n_chunks=n, multiple=m)
        self._assert_tiling(cuts, s, m)
        assert len(cuts) <= n


class TestZooServing:
    """Every arch serves end-to-end on a real Server: chunked admission
    produces exactly the bulk tokens, and paged exactly the contiguous
    ones where the arch pages."""

    def _serve(self, arch, *, prefill_chunk, paged=False, n_req=2,
               max_new=4):
        cfg, params = _server_params(arch)
        srv = Server(cfg, params, _mesh(), srv=ServerConfig(
            max_batch=2, max_seq=32, max_new_tokens=max_new,
            prefill_chunk=prefill_chunk, paged=paged, block_size=8))
        rng = np.random.default_rng(7)
        for i in range(n_req):
            plen = (11, 7)[i % 2]
            if cfg.family == "encdec":
                plen = min(plen, cfg.decoder_max_seq - max_new)
            prompt = rng.integers(0, cfg.vocab_size, size=plen)
            fe = (rng.standard_normal((cfg.frontend_tokens,
                                       cfg.frontend_dim),
                                      dtype=np.float32)
                  if cfg.frontend else None)
            srv.submit(prompt, frontend_embeds=fe)
        srv.run()
        assert len(srv.done) == n_req
        return {r.rid: r.out_tokens for r in srv.done}, srv.stats()

    @pytest.mark.parametrize("arch", ZOO)
    def test_chunked_tokens_equal_bulk(self, arch):
        chunked, stc = self._serve(arch, prefill_chunk=4)
        bulk, stb = self._serve(arch, prefill_chunk=None)
        assert chunked == bulk, arch
        assert str(stc["admission_mode"]).startswith("chunked("), arch
        assert stc["admission_fallback"] == ""
        assert stb["admission_mode"] == "bulk"

    @pytest.mark.parametrize("arch", PAGED_ARCHS)
    def test_paged_tokens_equal_contiguous(self, arch):
        paged, stp = self._serve(arch, prefill_chunk=4, paged=True)
        cont, _ = self._serve(arch, prefill_chunk=4, paged=False)
        assert paged == cont, arch

    def test_eff_chunk_rounds_to_carry_multiple(self):
        """A requested chunk below the SSD multiple admits at the rounded
        size (cuts must land on ssm_chunk boundaries for the state
        hand-off to be exact) — and stats says so."""
        _, stats = self._serve("mamba2-2.7b", prefill_chunk=4, n_req=1)
        mult = chunk_carry_spec(_zoo_cfg("mamba2-2.7b")).chunk_multiple
        assert stats["admission_mode"] == f"chunked({mult})"


class TestAdmissionFallback:
    """No silent bulk fallback: requesting chunked admission on a gated
    arch warns once at build time naming arch + reason, and the mode is
    queryable from stats()."""

    def _pallas_server(self):
        cfg, params = _server_params("smollm-360m")
        cfg = dataclasses.replace(cfg, attn_impl="pallas")
        return cfg, params

    def test_gated_arch_warns_with_reason(self):
        cfg, params = self._pallas_server()
        with pytest.warns(UserWarning, match="smollm-360m.*pallas"):
            srv = Server(cfg, params, _mesh(), srv=ServerConfig(
                max_batch=2, max_seq=32, max_new_tokens=2,
                prefill_chunk=4))
        stats = srv.stats()
        assert stats["admission_mode"] == "bulk"
        assert "pallas" in str(stats["admission_fallback"])

    def test_bulk_request_does_not_warn(self):
        """prefill_chunk=None is an explicit bulk ask — no warning, and
        the fallback reason says disabled-not-unsupported."""
        cfg, params = self._pallas_server()
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            srv = Server(cfg, params, _mesh(), srv=ServerConfig(
                max_batch=2, max_seq=32, max_new_tokens=2,
                prefill_chunk=None))
        assert not [w for w in rec if "chunked prefill" in str(w.message)]
        assert srv.stats()["admission_mode"] == "bulk"
        assert srv.stats()["admission_fallback"] == "prefill_chunk disabled"

    def test_supported_arch_does_not_warn(self):
        cfg, params = _server_params("smollm-360m")
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            srv = Server(cfg, params, _mesh(), srv=ServerConfig(
                max_batch=2, max_seq=32, max_new_tokens=2,
                prefill_chunk=4))
        assert not [w for w in rec if "chunked prefill" in str(w.message)]
        assert srv.stats()["admission_mode"] == "chunked(4)"


class TestMoEChunkBound:
    """Both directions of the chunk-local capacity bound."""

    def test_no_overflow_keeps_agree_and_exact(self):
        """capacity_factor >= n_experts: keep decisions agree everywhere
        (the identity precondition the conformance runs rely on)."""
        cfg, params = _setup("grok-1-314b")
        from repro.models.model import _embed
        toks = _tokens(cfg, 2, 13)
        x = _embed(cfg, params, toks, None)
        moe_p = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
        cuts = prefill_chunk_cuts(13, n_chunks=3)
        agree, _, _ = moe_chunk_agree_mask(cfg, moe_p, x, cuts)
        assert bool(jnp.all(agree))

    def test_tight_capacity_disagrees_and_mask_names_rows(self):
        """Contrapositive: a tight capacity makes chunk-local drop sets
        differ from bulk, the mask reports the rows, and serving_features
        already declared the arch chunked-but-inexact."""
        cfg = get_config("grok-1-314b").reduced()
        cfg = dataclasses.replace(cfg, capacity_factor=0.25)
        params = init_params(cfg, jax.random.PRNGKey(0))
        from repro.models.model import _embed
        toks = _tokens(cfg, 2, 16, key=3)
        x = _embed(cfg, params, toks, None)
        moe_p = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
        agree, keep_bulk, keep_chunk = moe_chunk_agree_mask(
            cfg, moe_p, x, prefill_chunk_cuts(16, n_chunks=4))
        assert not bool(jnp.all(agree)), \
            "tight capacity should make bulk and chunk-local drops differ"
        assert keep_bulk.shape == keep_chunk.shape
        assert not serving_features(cfg)["chunked_exact"]


class TestCapabilityTable:
    """The jax-free capability table is total and self-consistent."""

    @pytest.mark.parametrize("arch", ZOO)
    def test_spec_total_and_consistent(self, arch):
        cfg = get_config(arch).reduced()
        spec = chunk_carry_spec(cfg)
        feats = serving_features(cfg)
        assert spec.kind in ("ring", "latent", "state", "hybrid", "encdec")
        assert feats["chunked"]
        assert feats["chunked_exact"] == spec.exact
        assert spec.constant_size == (spec.kind == "state")
        if spec.kind in ("state", "hybrid"):
            assert spec.chunk_multiple == cfg.ssm_chunk
        assert supports_paged(cfg) == feats["paged"]
        if feats["prefix_cache"]:
            assert feats["paged"]
