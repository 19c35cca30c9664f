"""Distribution layer: sharding specs, chunked CE, train/serve/prefill
steps on a real (2,2) mesh, microbatch equivalence."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM, batch_specs
from repro.dist.loss import chunked_ce_loss
from repro.dist.sharding import (
    MeshAxes, batch_pspecs, cache_pspecs, opt_pspecs, param_pspecs)
from repro.dist.steps import (
    StepConfig, build_init, build_prefill_step, build_serve_step,
    build_train_step)
from repro.models.model import init_params, loss_fn


@pytest.fixture(scope="module")
def smollm():
    cfg = get_config("smollm-360m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


class TestShardingSpecs:
    def test_param_rules(self, mesh22, smollm):
        cfg, params = smollm
        specs = param_pspecs(cfg, mesh22, params)
        flat = {"/".join(str(getattr(k, "key", k)) for k in path): s
                for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]}
        # vocab-parallel embed (vocab 256 divisible by 2)
        assert flat["embed"] == P("model", "data")
        # column-parallel QKV on the stacked layer axis
        assert flat["layers/attn/wq"] == P(None, "data", "model")
        assert flat["layers/attn/wo"] == P(None, "model", "data")
        assert flat["layers/mlp/w_down"] == P(None, "model", "data")
        # norms replicated
        assert flat["layers/ln1/scale"] == P()

    def test_divisibility_fallback(self, mesh22):
        cfg = get_config("smollm-360m").reduced()
        cfg = dataclasses.replace(cfg, vocab_size=255)   # prime-ish
        shape = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))
        specs = param_pspecs(cfg, mesh22, shape)
        flat = {"/".join(str(getattr(k, "key", k)) for k in path): s
                for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]}
        assert flat["embed"][0] is None     # 255 % 2 != 0 -> dropped axis

    def test_opt_state_mirrors_params(self, mesh22, smollm):
        from repro.optim import AdamWConfig, adamw_init
        cfg, params = smollm
        pspecs = param_pspecs(cfg, mesh22, params)
        opt = jax.eval_shape(
            functools.partial(adamw_init, cfg=AdamWConfig()), params)
        ospecs = opt_pspecs(cfg, mesh22, opt, pspecs)
        assert ospecs["mu"]["embed"] == pspecs["embed"]
        assert ospecs["master"]["layers"]["attn"]["wq"] == \
            pspecs["layers"]["attn"]["wq"]
        assert ospecs["step"] == P()

    def test_cache_specs(self, mesh22):
        from repro.models.decode import init_cache
        cfg = get_config("smollm-360m").reduced()
        shape = jax.eval_shape(functools.partial(init_cache, cfg, 4, 32))
        specs = cache_pspecs(cfg, mesh22, shape)
        assert specs["k"] == P(None, "data", None, "model", None)
        assert specs["pos"] == P()

    def test_batch_specs(self, mesh22):
        b = batch_specs(16, 8, 100)
        specs = batch_pspecs(mesh22, b)
        assert specs["tokens"] == P("data", None)

    def test_multipod_axes(self):
        ax = MeshAxes(data=("pod", "data"))
        assert ax.model == "model"


class TestChunkedCE:
    def test_matches_full_loss(self, smollm):
        cfg, params = smollm
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        full, m_full = loss_fn(cfg, params, batch)
        for chunk in (4, 5, 16, 64):
            got, m = chunked_ce_loss(cfg, params, batch, seq_chunk=chunk)
            np.testing.assert_allclose(float(got), float(full),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(m["ce"]), float(m_full["ce"]),
                                       rtol=1e-5, atol=1e-6)

    def test_masked_labels_ignored(self, smollm):
        cfg, params = smollm
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab_size)
        labels = jnp.full_like(toks, -1).at[:, :4].set(toks[:, :4])
        loss, m = chunked_ce_loss(cfg, params,
                                  {"tokens": toks, "labels": labels},
                                  seq_chunk=8)
        assert float(m["tokens"]) == 8.0
        assert np.isfinite(float(loss))


class TestTrainStep:
    def _bundle(self, mesh, cfg, m=1, gb=8, s=16):
        scfg = StepConfig(microbatches=m, seq_chunk=8, warmup_steps=2,
                          total_steps=20, peak_lr=1e-3)
        bshape = batch_specs(s, gb, cfg.vocab_size)
        return build_train_step(cfg, mesh, scfg, bshape), scfg

    def test_loss_decreases(self, mesh22):
        cfg = get_config("smollm-360m").reduced()
        bundle, scfg = self._bundle(mesh22, cfg)
        init_fn, _ = build_init(cfg, mesh22, scfg)
        params, opt = init_fn(jax.random.PRNGKey(0))
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=17,
                                      global_batch=8))
        losses = []
        for step in range(8):
            params, opt, metrics = bundle.fn(params, opt,
                                             data.global_batch(step),
                                             jnp.int32(step))
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_microbatch_equivalence(self, mesh22):
        """m=1 and m=4 must produce the same update (grad averaging)."""
        cfg = get_config("smollm-360m").reduced()
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=17,
                                      global_batch=8))
        batch = data.global_batch(0)
        outs = []
        for m in (1, 4):
            bundle, scfg = self._bundle(mesh22, cfg, m=m)
            init_fn, _ = build_init(cfg, mesh22, scfg)
            params, opt = init_fn(jax.random.PRNGKey(0))
            p2, o2, metrics = bundle.fn(params, opt, batch, jnp.int32(0))
            outs.append((p2, float(metrics["loss"])))
        l1, l4 = outs[0][1], outs[1][1]
        np.testing.assert_allclose(l1, l4, rtol=1e-5)
        p1 = jax.tree.leaves(outs[0][0])
        p4 = jax.tree.leaves(outs[1][0])
        for a, b in zip(p1, p4):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-4, atol=2e-5)

    def test_moe_train_step(self, mesh22):
        cfg = get_config("grok-1-314b").reduced()
        bundle, scfg = self._bundle(mesh22, cfg, m=2)
        init_fn, _ = build_init(cfg, mesh22, scfg)
        params, opt = init_fn(jax.random.PRNGKey(0))
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=17,
                                      global_batch=8))
        params, opt, metrics = bundle.fn(params, opt, data.global_batch(0),
                                         jnp.int32(0))
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["moe_aux"]) > 0


class TestServePrefill:
    def test_serve_step_runs_sharded(self, mesh22):
        cfg = get_config("smollm-360m").reduced()
        scfg = StepConfig()
        bundle = build_serve_step(cfg, mesh22, scfg, batch=4, max_seq=32)
        init_fn, _ = build_init(cfg, mesh22, scfg)
        params, _ = init_fn(jax.random.PRNGKey(0))
        from repro.dist.sharding import to_shardings
        from repro.models.decode import init_cache
        csh = to_shardings(mesh22, bundle.in_specs[1])
        cache = jax.jit(lambda: init_cache(cfg, 4, 32),
                        out_shardings=csh)()
        toks = jnp.zeros((4,), jnp.int32)
        for _ in range(3):
            cache, logits = bundle.fn(params, cache, toks)
        assert np.asarray(cache["pos"]).tolist() == [3] * 4
        assert logits.shape == (4, cfg.vocab_size)

    def test_prefill_step_matches_unsharded(self, mesh22):
        cfg = get_config("smollm-360m").reduced()
        scfg = StepConfig()
        bundle = build_prefill_step(cfg, mesh22, scfg, batch=4, seq_len=16)
        init_fn, _ = build_init(cfg, mesh22, scfg)
        params, _ = init_fn(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                  cfg.vocab_size)
        cache, logits = bundle.fn(params, toks)
        from repro.models.prefill import prefill
        params_local = jax.device_get(params)
        cache_ref, logits_ref = prefill(cfg, params_local, toks,
                                        cache_len=16)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(logits_ref),
                                   rtol=2e-4, atol=2e-4)


class TestShardedPallasAttention:
    """The flash kernel inside a shard_map (GSPMD cannot partition a
    Mosaic kernel): the sharded call equals the unsharded one."""

    def _cfg(self):
        cfg = get_config("smollm-360m").reduced()
        return dataclasses.replace(cfg, attn_impl="pallas")

    @pytest.mark.parametrize("shape", [(2, 2), (1, 4), (4, 1)])
    def test_runner_equals_unsharded(self, shape):
        from repro.dist.steps import _attention_runner
        from repro.kernels.flash_attention import flash_attention
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(*shape)
        runner = _attention_runner(self._cfg(), mesh)
        q = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 40, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (4, 4 // 2, 40, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (4, 4 // 2, 40, 16))
        got = jax.jit(functools.partial(runner, causal=True, window=None,
                                        scale=None))(q, k, v)
        want = flash_attention(q, k, v, causal=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_one_device_calls_kernel_directly(self):
        from repro.dist.steps import _attention_runner
        from repro.launch.mesh import make_host_mesh

        assert _attention_runner(self._cfg(), make_host_mesh(1, 1)) is None
        jnp_cfg = get_config("smollm-360m").reduced()
        assert _attention_runner(jnp_cfg, make_host_mesh(2, 2)) is None

    def test_prefill_step_matches_unsharded(self, mesh22):
        cfg = self._cfg()
        bundle = build_prefill_step(cfg, mesh22, StepConfig(), batch=4,
                                    seq_len=16)
        init_fn, _ = build_init(cfg, mesh22, StepConfig())
        params, _ = init_fn(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                  cfg.vocab_size)
        _, logits = bundle.fn(params, toks)
        from repro.models.prefill import prefill
        _, logits_ref = prefill(cfg, jax.device_get(params), toks,
                                cache_len=16)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(logits_ref),
                                   rtol=2e-4, atol=2e-4)


class TestArtTP:
    """The paper's technique as a training feature: ART ring schedules for
    TP collectives must be numerically identical to the GSPMD baseline and
    structurally all-reduce-free at the layer level."""

    def test_art_tp_matches_baseline(self, mesh22):
        cfg = get_config("nemotron-4-340b").reduced()
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=17,
                                      global_batch=8))
        batch = data.global_batch(0)
        bshape = batch_specs(16, 8, cfg.vocab_size)
        outs = {}
        for art in (False, True):
            scfg = StepConfig(microbatches=2, seq_chunk=8, art_tp=art,
                              warmup_steps=2, total_steps=10)
            bundle = build_train_step(cfg, mesh22, scfg, bshape)
            init_fn, _ = build_init(cfg, mesh22, scfg)
            params, opt = init_fn(jax.random.PRNGKey(0))
            _, _, m = bundle.fn(params, opt, batch, jnp.int32(0))
            outs[art] = (float(m["loss"]), float(m["grad_norm"]))
        np.testing.assert_allclose(outs[False][0], outs[True][0], rtol=1e-4)
        np.testing.assert_allclose(outs[False][1], outs[True][1], rtol=2e-3)

    def test_art_layer_eliminates_all_reduce(self):
        from benchmarks.artlayer import LayerDims, compare
        d = LayerDims(d_model=256, n_heads=8, n_kv=4, head_dim=32,
                      d_ff=512, seq=128, batch=1)
        out = compare(d)
        assert out["art"]["by_op"].get("all-reduce", 0) == 0
        assert out["gspmd"]["by_op"].get("all-reduce", 0) > 0


class TestCrossPodGradSync:
    """Compressed cross-pod gradient sync: correctness + int8 wire."""

    @pytest.fixture(scope="class")
    def podmesh(self):
        return jax.make_mesh((2, 2), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)

    def test_uncompressed_matches_mean(self, podmesh):
        from repro.dist.grad_sync import cross_pod_all_reduce
        g = {"w": jnp.arange(8.0).reshape(2, 4)}
        gs = jax.device_put(g["w"], jax.sharding.NamedSharding(
            podmesh, P("pod", None)))
        out, _ = cross_pod_all_reduce({"w": gs}, podmesh)
        want = (np.asarray(g["w"][:1]) + np.asarray(g["w"][1:])) / 2
        got = np.asarray(out["w"])
        np.testing.assert_allclose(got[0], want[0])
        np.testing.assert_allclose(got[1], want[0])

    def test_compressed_close_and_ef_tracks(self, podmesh):
        from repro.dist.grad_sync import cross_pod_all_reduce
        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (2, 256))
        gs = jax.device_put(g, jax.sharding.NamedSharding(
            podmesh, P("pod", None)))
        out, ef = cross_pod_all_reduce({"w": gs}, podmesh, compressed=True)
        want = np.broadcast_to(np.asarray(g).mean(0, keepdims=True), (2, 256))
        got = np.asarray(out["w"])
        err = np.abs(got - want).max()
        scale = np.abs(np.asarray(g)).max() / 127
        assert err <= 2 * scale + 1e-6, (err, scale)
        assert np.abs(np.asarray(ef["w"])).max() <= scale + 1e-6

    def test_int8_on_the_wire(self, podmesh):
        from repro.dist.grad_sync import cross_pod_all_reduce
        g = jnp.zeros((2, 512))
        gs = jax.device_put(g, jax.sharding.NamedSharding(
            podmesh, P("pod", None)))
        lowered = jax.jit(lambda t: cross_pod_all_reduce(
            {"w": t}, podmesh, compressed=True)[0]).lower(gs)
        txt = lowered.compile().as_text()
        assert "s8[" in txt, "compressed sync must move int8 payloads"

    def test_wire_bytes_saving(self):
        from repro.dist.grad_sync import wire_bytes
        n = 1 << 20
        ratio = wire_bytes(n, compressed=False) / wire_bytes(n, compressed=True)
        assert ratio > 3.8
