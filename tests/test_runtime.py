"""Runtime: fault-tolerant trainer (restart, preemption, watchdog),
elastic re-meshing, continuous-batching server."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.dist.steps import StepConfig
from repro.runtime.elastic import ElasticMesh, remesh, viable_mesh_shapes
from repro.runtime.server import Server, ServerConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def _trainer(tmp_path, total=6, interval=2, mesh=None, seed=0):
    cfg = get_config("smollm-360m").reduced()
    scfg = StepConfig(microbatches=1, seq_chunk=8, warmup_steps=2,
                      total_steps=total, peak_lr=1e-3)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=17,
                                  global_batch=4, seed=seed))
    tcfg = TrainerConfig(total_steps=total, ckpt_dir=str(tmp_path / "ck"),
                         ckpt_interval=interval, log_interval=100)
    return Trainer(cfg, scfg, tcfg, data, mesh=mesh, log_fn=lambda s: None)


class TestTrainerRestart:
    def test_restart_resumes_from_checkpoint(self, tmp_path, mesh22):
        t1 = _trainer(tmp_path, total=4, interval=2, mesh=mesh22)
        t1.train()
        losses_a = [h["loss"] for h in t1.history]

        # a "crashed and restarted" trainer picks up at step 4 (last ckpt)
        t2 = _trainer(tmp_path, total=6, interval=2, mesh=mesh22)
        t2.train()
        assert t2.history[0]["step"] == 5      # resumed after step-4 ckpt
        assert len(t2.history) == 2            # only steps 5..6 run

    def test_restart_trajectory_identical(self, tmp_path, mesh22):
        """Determinism: (run 6) == (run 4, restart, run to 6) losses."""
        t_full = _trainer(tmp_path / "a", total=6, interval=100, mesh=mesh22)
        t_full.train()
        full = [round(h["loss"], 5) for h in t_full.history]

        t1 = _trainer(tmp_path / "b", total=4, interval=4, mesh=mesh22)
        t1.train()
        t2 = _trainer(tmp_path / "b", total=6, interval=4, mesh=mesh22)
        t2.train()
        resumed = [round(h["loss"], 5) for h in t1.history] + \
                  [round(h["loss"], 5) for h in t2.history]
        np.testing.assert_allclose(full, resumed[: len(full)], rtol=1e-3)

    def test_preemption_checkpoints_and_exits(self, tmp_path, mesh22):
        t = _trainer(tmp_path, total=50, interval=100, mesh=mesh22)
        steps_seen = []

        def on_step(step, m):
            steps_seen.append(step)
            if step == 3:
                t._preempted = True     # simulate SIGTERM

        t.train(on_step=on_step)
        assert max(steps_seen) == 3
        assert t.ckpt.latest_step() == 3


class TestTrainerFailures:
    def test_untyped_exception_propagates(self, tmp_path, mesh22):
        """Only a RankFailure triggers elastic recovery: anything else (a
        compile error, an OOM) fails the run instead of looping."""

        class Boom:
            calls = 0

            def on_step(self, step, op):
                Boom.calls += 1
                raise ValueError("not a rank failure")

        t = _trainer(tmp_path, mesh=mesh22)
        t.fault_plan = Boom()
        with pytest.raises(ValueError, match="not a rank failure"):
            t.train()
        assert Boom.calls == 1
        assert t.elastic is None and t.history == []


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_dir_wins(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_checkout_root_otherwise(self, monkeypatch):
        import pathlib

        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = pathlib.Path(__file__).resolve().parents[1]
        path = enable_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path       # fixed across calls


class TestWatchdog:
    def test_flags_stragglers(self, tmp_path, mesh22):
        t = _trainer(tmp_path, mesh=mesh22)
        t.tcfg = t.tcfg
        for _ in range(10):
            assert not t._watch_step_time(0.1)
        # three consecutive 10x-slow steps exhaust the budget
        assert not t._watch_step_time(1.0)
        assert not t._watch_step_time(1.0)
        assert t._watch_step_time(1.0)

    def test_recovers_after_normal_step(self, tmp_path, mesh22):
        t = _trainer(tmp_path, mesh=mesh22)
        for _ in range(10):
            t._watch_step_time(0.1)
        t._watch_step_time(1.0)
        t._watch_step_time(0.1)       # strike reset
        assert t._straggler_strikes == 0


class TestElastic:
    def test_viable_shapes(self):
        shapes = viable_mesh_shapes(8, model=2)
        assert shapes[0] == (4, 2)

    def test_remesh_drops_devices(self):
        devs = jax.devices()
        m = remesh(devs, model=2)
        assert m.shape["model"] == 2
        assert m.shape["data"] == len(devs) // 2

    def test_elastic_fail_shrinks_data_axis(self):
        em = ElasticMesh(model=2)
        m0 = em.mesh()
        m1 = em.fail(0, 1)
        assert m1.shape["data"] == m0.shape["data"] - 1

    def test_fail_below_tp_raises(self):
        n = len(jax.devices())
        em = ElasticMesh(model=n)       # TP spans every device
        with pytest.raises(RuntimeError):
            em.fail(0)      # n-1 devices cannot keep TP=n


class TestServer:
    def _server(self, mesh, **kw):
        from repro.dist.sharding import param_pspecs, to_shardings
        from repro.models.model import init_params
        cfg = get_config("smollm-360m").reduced()
        shape = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))
        psh = to_shardings(mesh, param_pspecs(cfg, mesh, shape))
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=psh)(jax.random.PRNGKey(0))
        return cfg, params, Server(cfg, params, mesh, srv=ServerConfig(
            max_batch=2, max_seq=64, max_new_tokens=4, **kw))

    def test_all_requests_complete(self, mesh22):
        cfg, params, srv = self._server(mesh22)
        rng = np.random.default_rng(0)
        for _ in range(5):
            srv.submit(rng.integers(0, cfg.vocab_size, size=6))
        srv.run()
        assert len(srv.done) == 5
        assert all(len(r.out_tokens) == 4 for r in srv.done)
        s = srv.stats()
        assert s["tokens"] == 20 and s["throughput_tok_s"] > 0

    def test_output_matches_unbatched_greedy(self, mesh22):
        """Continuous batching must not change any request's tokens —
        including with mixed prompt lengths in flight (per-slot positions)
        and chunked prefill admission."""
        from repro.models.decode import decode_step
        from repro.models.prefill import prefill
        cfg, params, srv = self._server(mesh22, prefill_chunk=4)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, cfg.vocab_size, size=s)
                   for s in (6, 9, 5)]
        for p in prompts:
            srv.submit(p)
        srv.run()

        params_local = jax.device_get(params)
        by_rid = {r.rid: r for r in srv.done}
        for rid, p in enumerate(prompts):
            cache, logits = prefill(cfg, params_local,
                                    jnp.asarray(p[None, :]), cache_len=64)
            out = []
            for _ in range(4):
                nxt = int(jnp.argmax(logits, -1)[0])
                out.append(nxt)
                cache, logits = decode_step(cfg, params_local, cache,
                                            jnp.asarray([nxt], jnp.int32))
            assert out == by_rid[rid].out_tokens, (out, by_rid[rid])

    def test_chunked_admission_equals_bulk(self, mesh22):
        """Chunked prefill admission must be token-identical to bulk
        per-slot admission (the bit-identity claim at the scheduler
        level)."""
        outs = {}
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 1000, size=s) for s in (11, 4, 7)]
        for chunk in (None, 3):
            cfg, params, srv = self._server(mesh22, prefill_chunk=chunk)
            for p in prompts:
                srv.submit(p % cfg.vocab_size)
            srv.run()
            outs[chunk] = {r.rid: r.out_tokens for r in srv.done}
        assert outs[None] == outs[3]

    def test_ttft_stamped_at_first_decode_token(self, mesh22):
        """``first_token`` stamps when the first decode token id exists —
        not at prefill completion, and never before the final prefill
        chunk under chunked admission."""
        cfg, params, srv = self._server(mesh22, prefill_chunk=3)
        rng = np.random.default_rng(3)
        srv.submit(rng.integers(0, cfg.vocab_size, size=8))  # 3 chunks
        # two ticks run two prefill chunks; no token exists yet
        srv.step()
        srv.step()
        req = srv.slots[0]
        assert req is not None and req.phase == "prefill"
        assert req.first_token is None and not req.out_tokens
        before = time.perf_counter()
        srv.step()          # final chunk: first token sampled here
        assert req.out_tokens and req.first_token is not None
        assert req.first_token >= before
        srv.run()
        assert req.finished is not None
        assert req.submitted <= req.first_token <= req.finished
        s = srv.stats()
        assert s["mean_ttft_s"] > 0 and s["mean_itl_s"] >= 0
