"""Compile rehearsals for a TPU v5e: the kernels of the main path at real
widths, lowered by the chip's compiler for a described (not attached)
topology.

Nothing here runs on a chip; a compile that passes proves the kernel
lowers (block shapes, memory spaces, remote-DMA addressing) and holds a
``tpu_custom_call``, which interpret mode cannot show.  The topology is
described inside a fixture, so only the worker that runs this file loads
the TPU compiler; where it cannot be described every test skips.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler would otherwise write its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip cannot be read back without one, so
    the persistent cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _attn_shapes(cfg, sharding, batch, seq):
    hd = cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((batch, cfg.n_heads, seq, hd), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((batch, cfg.n_kv_heads, seq, hd),
                              jnp.bfloat16, sharding=sharding)
    return q, kv, kv


def test_flash_forward_smollm(one_chip):
    from repro.kernels.flash_attention import flash_attention

    cfg = get_config("smollm-360m")
    _compile(functools.partial(flash_attention, causal=True,
                               interpret=False),
             *_attn_shapes(cfg, one_chip, 1, 2048))


def test_flash_grad_smollm(one_chip):
    from repro.kernels.flash_attention import flash_attention

    cfg = get_config("smollm-360m")

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    # value and grad, as the train step takes them: the loss keeps the
    # kernel's forward, the custom VJP supplies the backward
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
             *_attn_shapes(cfg, one_chip, 1, 2048))


def test_flash_sliding_window_danube(one_chip):
    from repro.kernels.flash_attention import flash_attention

    cfg = get_config("h2o-danube-1.8b")
    _compile(functools.partial(flash_attention, causal=True,
                               window=cfg.window, interpret=False),
             *_attn_shapes(cfg, one_chip, 1, 4608))


def test_ssd_mamba2(one_chip):
    from repro.kernels.ssd import ssd

    cfg = get_config("mamba2-2.7b")
    b, s = 1, 2048
    h, p, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, \
        cfg.ssm_state
    f32, bf16 = jnp.float32, jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _compile(functools.partial(ssd, chunk=cfg.ssm_chunk, interpret=False),
             sds((b, s, h, p), bf16), sds((b, s, h), f32), sds((h,), f32),
             sds((b, s, g, n), bf16), sds((b, s, g, n), bf16),
             sds((h,), f32))


@pytest.mark.parametrize("bidirectional", [False, True])
@pytest.mark.parametrize("op", ["allgather", "reducescatter"])
def test_cc_matmul_remote_dma_2x2(topo, op, bidirectional):
    from repro.kernels.cc_matmul import (allgather_matmul_pallas,
                                         matmul_reducescatter_pallas)

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    n, b_loc, k, m = 2, 256, 1024, 512
    if op == "allgather":
        fn = allgather_matmul_pallas
        xs, ws = (n * b_loc, k), (k, n * m)
        xspec, wspec, ospec = P("model", None), P(None, "model"), \
            P(None, "model")
    else:
        fn = matmul_reducescatter_pallas
        xs, ws = (n * b_loc, n * k), (n * k, m)
        xspec, wspec, ospec = P(None, "model"), P("model", None), \
            P("model", None)
    f = jax.shard_map(
        functools.partial(fn, axis="model", bidirectional=bidirectional,
                          interpret=False, use_remote_dma=True),
        mesh=mesh, in_specs=(xspec, wspec), out_specs=ospec, check_vma=False)
    compiled = _compile(
        f,
        jax.ShapeDtypeStruct(xs, jnp.bfloat16,
                             sharding=NamedSharding(mesh, xspec)),
        jax.ShapeDtypeStruct(ws, jnp.bfloat16,
                             sharding=NamedSharding(mesh, wspec)))
    # the ring runs inside the kernel: no ppermute hop was emitted
    assert "collective-permute" not in compiled.as_text()


def test_sharded_attention_1x4(topo, monkeypatch):
    """The shard_map-wrapped Pallas attention the step builders install:
    GSPMD alone refuses to partition the Mosaic kernel."""
    from repro.dist.steps import _attention_runner

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    cfg = dataclasses.replace(get_config("h2o-danube-1.8b"),
                              attn_impl="pallas")
    runner = _attention_runner(cfg, mesh)
    act = NamedSharding(mesh, P(None, "model", None, None))
    _compile(functools.partial(runner, causal=True, window=cfg.window,
                               scale=None),
             *_attn_shapes(cfg, act, 2, 2048))


def test_decode_step_updates_ring_in_place_smollm(topo):
    """The serving cell's decode step at 64 slots x 2048: the K/V ring
    rides the layer loop in place, so the program holds no second ring in
    temporaries and copies no layer's slab nor the whole ring."""
    import re

    from repro.dist import steps
    from repro.dist.sharding import to_shardings

    cfg = get_config("smollm-360m")
    batch, max_seq = 64, 2048
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    bundle = steps.build_serve_step(cfg, mesh, steps.StepConfig(),
                                    batch=batch, max_seq=max_seq,
                                    sample=True)

    def sds(shapes, specs):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, to_shardings(mesh, specs))

    cache_shape = bundle.aux["cache_shape"]
    compiled = bundle.fn.lower(
        sds(bundle.aux["params_shape"], bundle.in_specs[0]),
        sds(cache_shape, bundle.in_specs[1]),
        jax.ShapeDtypeStruct((batch,), jnp.int32)).compile()

    kv_bytes = sum(cache_shape[k].size * cache_shape[k].dtype.itemsize
                   for k in ("k", "v"))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < kv_bytes / 8, (temp, kv_bytes)

    ring = cache_shape["k"].shape                  # (L, B, Hkv, S_buf, hd)
    whole = (sorted(ring), sorted(ring[1:]))       # ring, one layer's slab
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* copy\(", compiled.as_text()):
        dims = [int(d) for d in m.group(1).split(",") if d and d != "1"]
        assert sorted(dims) not in whole, m.group(0)


def test_decode_step_updates_latent_ring_in_place_deepseek(topo):
    """The deepseek-v2-lite cell's decode step at 128 slots x 4096 (the
    chip's cut: the dense layer and 6 MoE layers, 16 of 64 experts held):
    the latent rings ride the layer loops in place, so the temporaries
    are far below the 4.2 GB of rings and no copy holds a whole ring or
    one layer's slab of it."""
    import re

    from repro.dist import steps
    from repro.dist.sharding import to_shardings

    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), n_layers=7,
                              experts_held=16)
    batch, max_seq = 128, 4096
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    bundle = steps.build_serve_step(cfg, mesh, steps.StepConfig(),
                                    batch=batch, max_seq=max_seq,
                                    sample=True)

    def sds(shapes, specs):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, to_shardings(mesh, specs))

    cache_shape = bundle.aux["cache_shape"]
    compiled = bundle.fn.lower(
        sds(bundle.aux["params_shape"], bundle.in_specs[0]),
        sds(cache_shape, bundle.in_specs[1]),
        jax.ShapeDtypeStruct((batch,), jnp.int32)).compile()

    ring_bytes = sum(cache_shape[k].size * cache_shape[k].dtype.itemsize
                     for k in ("ckv", "krope"))
    assert ring_bytes > 4.2e9
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < ring_bytes / 64, (temp, ring_bytes)

    whole = []
    for k in ("ckv", "krope"):
        ring = cache_shape[k].shape               # (L, B, S_buf, d)
        whole += [sorted(ring), sorted(ring[1:])]
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* copy\(", compiled.as_text()):
        dims = [int(d) for d in m.group(1).split(",") if d and d != "1"]
        assert sorted(dims) not in whole, m.group(0)
