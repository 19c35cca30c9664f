"""A whole run, with the harness's look for a chip skipped, on the tiny
stand-in: sound, it is correct; with the timed path broken underneath,
``correct`` comes out false.  One case for each fault the serving cell
can have (it runs on one chip, so no exchange between chips is left out,
and it has no batch mean to take over half the rows)."""

import functools

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import tiny

SEED = 2**31 + 4242


def _run(seconds=1.0, window=None):
    return run.execute("smollm-360m.chat", SEED, seconds, False,
                       require_chip=False, overrides=tiny.overrides(window))


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def token_altered(build):
    """The decode step returns each sampled id plus one."""
    @functools.wraps(build)
    def wrapped(cfg, *a, **kw):
        b = build(cfg, *a, **kw)
        fn = b.fn

        def broken(params, cache, toks):
            cache, ids = fn(params, cache, toks)
            return cache, (ids + 1) % cfg.vocab_size

        b.fn = broken
        return b
    return wrapped


def cache_unchanged(build):
    """The decode step returns the cache it was given."""
    @functools.wraps(build)
    def wrapped(*a, **kw):
        b = build(*a, **kw)
        fn = b.fn

        def broken(params, cache, toks):
            keep = _copy(cache)
            _, ids = fn(params, cache, toks)
            return keep, ids

        b.fn = broken
        return b
    return wrapped


@pytest.mark.parametrize("window", [None, 16])
def test_sound_run_is_correct(window):
    """Windowed serving wraps the K/V ring: prompts reach 48 tokens."""
    out = _run(window=window)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", [token_altered, cache_unchanged])
def test_serving_fault_is_caught(monkeypatch, fault):
    import repro.runtime.server as server

    monkeypatch.setattr(server, "build_serve_step",
                        fault(server.build_serve_step))
    out = _run()
    assert not out["correct"], out["checks"]


def test_control_reads_not_correct():
    """The fp8 control, read on the requests of a sound run, goes through
    the comparison that decides ``correct`` and fails it."""
    from chipbench import serve

    out = _run()
    assert out["correct"], out["checks"]
    rec = out["_record"]
    wl = tiny.overrides()["workload"]
    ctl, n = serve.checks(wl, rec["hf"], SEED, rec["recs"], precision="fp8")
    assert n > 0
    assert not run.verdict(ctl), ctl
