"""Chip smoke test: the serving and training paths at published widths on
a TPU, driven through the normal entry points in this one process.

    python chip_smoke.py             # one chip: serve, prefill check, train
    python chip_smoke.py --chips 4   # four chips: PGAS, conduit collectives,
                                     # fused matmuls, a tensor-parallel model

Run it from the checkout root.  It refuses to run anywhere but on a TPU (and
with Pallas forced into interpret mode): it exits non-zero before any phase.
A chip belongs to one process, so every phase runs here, in-process —
``launch/serve.main`` and ``launch/train.main`` take an argv list.  Every
phase checks its own result and raises on a mismatch; nothing is caught, so
a failed phase exits non-zero and never reaches the last line.  The last
line of stdout is ``{"ok": true, "device": {...}}`` as JAX reports the
device.  Earlier lines report compile seconds, device memory, serving
tokens/s, train step seconds and the attention implementation each step
resolved to.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import tempfile
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.abspath(__file__))
SMOLLM = "smollm-360m"
DANUBE = "h2o-danube-1.8b"

#: normalized L2 error allowed between the Pallas and jnp prefill logits of
#: one model (bf16 weights and activations; both attention paths accumulate
#: in f32, so they differ only in summation order)
PREFILL_REL_TOL = 2e-2
#: the same bound between the model on four chips (tensor-parallel) and on
#: one chip, for prefill and every decode step: the row-parallel matmuls
#: sum bf16 partials across chips where one chip accumulates in f32, so
#: the two differ by bf16 rounding per layer; a wrong sharding is off by
#: O(1)
SHARDED_REL_TOL = 5e-2

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


class Failed(RuntimeError):
    """A phase's result did not match its reference."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise Failed(msg)


# ---------------------------------------------------------------------------
# platform gate and instrumentation
# ---------------------------------------------------------------------------


def require_tpu(chips: int):
    """Exit non-zero unless JAX finds ``chips`` TPU devices and Pallas is
    not forced into interpret mode.  Runs before any phase."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found {len(devices)} "
                 f"{devices[0].platform} device(s); this check runs only on "
                 f"the chip")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devices)}")
    from repro.kernels.common import INTERPRET_ENV, should_interpret

    if should_interpret():
        sys.exit(f"chip_smoke: {INTERPRET_ENV}="
                 f"{os.environ.get(INTERPRET_ENV)!r} forces the Pallas "
                 f"interpreter; unset it to run the kernels")
    return devices


class CompileLog:
    """Backend compile seconds per jitted function, read from JAX's own
    monitoring events (a persistent-cache hit records no compile)."""

    def __init__(self):
        self._spans = []
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self._spans.append((kw.get("fun_name", "?"), duration))

    def report(self, phase: str) -> None:
        per_fn = defaultdict(float)
        for name, d in self._spans:
            per_fn[name] += d
        self._spans = []
        total = sum(per_fn.values())
        top = sorted(per_fn.items(), key=lambda kv: -kv[1])[:6]
        body = ", ".join(f"{n} {d:.1f}s" for n, d in top)
        log(f"[{phase}] compile {total:.1f}s over {len(per_fn)} programs: "
            f"{body}")


def report_memory(phase: str, compiled=None, what: str = "") -> None:
    import jax

    if compiled is not None:
        m = compiled.memory_analysis()
        gib = 1 << 30
        log(f"[{phase}] memory_analysis({what}): args "
            f"{m.argument_size_in_bytes / gib:.3f} GiB, temp "
            f"{m.temp_size_in_bytes / gib:.3f} GiB, out "
            f"{m.output_size_in_bytes / gib:.3f} GiB, aliased "
            f"{m.alias_size_in_bytes / gib:.3f} GiB")
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            log(f"[{phase}] {d}: peak_bytes_in_use "
                f"{stats['peak_bytes_in_use'] / (1 << 30):.3f} GiB, "
                f"bytes_in_use {stats.get('bytes_in_use', 0) / (1 << 30):.3f}"
                f" GiB")


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


# ---------------------------------------------------------------------------
# one chip: serve, prefill check, train
# ---------------------------------------------------------------------------


def phase_serve(arch: str = SMOLLM, full: bool = True, requests: int = 16,
                prompt_len: int = 512, max_new: int = 32,
                max_batch: int = 8, max_seq: int = 2048):
    """Serve ``requests`` prompts through ``launch/serve.main``; every one
    must finish with ``max_new`` tokens.  Returns the server."""
    import numpy as np

    from repro.launch import serve
    from repro.models.layers import resolve_attn_impl

    argv = ["--arch", arch, "--data-axis", "1", "--model-axis", "1",
            "--max-batch", str(max_batch), "--max-seq", str(max_seq),
            "--requests", str(requests), "--prompt-len", str(prompt_len),
            "--max-new", str(max_new)]
    if full:
        argv.append("--full")
    t0 = time.perf_counter()
    srv = serve.main(argv)
    wall = time.perf_counter() - t0
    cfg = srv.cfg
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab_size}, {cfg.param_dtype}; "
        f"prefill attention {resolve_attn_impl(cfg)}, decode attention "
        f"jnp (models/decode.py)")
    stats = srv.stats()
    check(len(srv.done) == requests,
          f"{len(srv.done)} of {requests} requests finished")
    short = [(r.rid, len(r.out_tokens)) for r in srv.done
             if len(r.out_tokens) != max_new]
    check(not short, f"requests without {max_new} tokens: {short}")
    log(f"[serve] {stats['requests']} requests x {max_new} tokens; "
        f"{stats['throughput_tok_s']:.1f} tok/s incl. compile (launcher "
        f"wall {wall:.1f}s), admission_mode {stats['admission_mode']}"
        + (f" ({stats['admission_fallback']})"
           if stats["admission_fallback"] else ""))
    # the same traffic again on the warm server: no compile in this window
    rng = np.random.default_rng(1)
    n_done = len(srv.done)
    t0 = time.perf_counter()
    for _ in range(requests):
        srv.submit(rng.integers(0, cfg.vocab_size, size=prompt_len))
    steps = srv.run()
    wall = time.perf_counter() - t0
    warm = srv.done[n_done:]
    check(len(warm) == requests and all(len(r.out_tokens) == max_new
                                        for r in warm),
          f"warm pass: {len(warm)} of {requests} requests finished with "
          f"{max_new} tokens")
    log(f"[serve] warm pass: {requests} requests x {max_new} tokens in "
        f"{steps} scheduler steps, {wall:.3f}s, "
        f"{requests * max_new / wall:.1f} tok/s (host clock, compile-free)")
    compiled = srv.bundle.fn.lower(srv.params, srv.cache,
                                   np.zeros((max_batch,), np.int32)).compile()
    report_memory("serve", compiled, "decode step")
    return srv


def phase_prefill_check(params, mesh, arch: str = SMOLLM,
                        full: bool = True, prompt_len: int = 512,
                        seed: int = 0) -> None:
    """Prefill logits of one prompt with ``attn_impl="pallas"`` against
    ``"jnp"``; the Pallas program must contain the compiled kernel."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.dist.steps import StepConfig, build_prefill_step

    cfg = get_config(arch)
    if not full:
        cfg = cfg.reduced()
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(1, prompt_len)).astype(np.int32)
    logits = {}
    for impl in ("pallas", "jnp"):
        c = dataclasses.replace(cfg, attn_impl=impl)
        bundle = build_prefill_step(c, mesh, StepConfig(), batch=1,
                                    seq_len=prompt_len)
        t0 = time.perf_counter()
        compiled = bundle.fn.lower(params, toks).compile()
        dt = time.perf_counter() - t0
        kernels = compiled.as_text().count("tpu_custom_call")
        log(f"[prefill] attn_impl={impl}: compiled in {dt:.1f}s, "
            f"{kernels} tpu_custom_call op(s)")
        if impl == "pallas":
            check(kernels > 0, "the Pallas prefill holds no tpu_custom_call:"
                  " the kernel did not lower for the chip")
            report_memory("prefill", compiled, "pallas prefill")
        _, out = compiled(params, toks)
        logits[impl] = np.asarray(jax.device_get(out), np.float32)
    for impl, v in logits.items():
        check(bool(np.isfinite(v).all()), f"non-finite {impl} logits")
    err = rel_err(logits["pallas"], logits["jnp"])
    diff = float(np.abs(logits["pallas"] - logits["jnp"]).max())
    log(f"[prefill] pallas vs jnp logits: rel L2 {err:.3e} (tol "
        f"{PREFILL_REL_TOL:g}), max |diff| {diff:.3e}, max |logit| "
        f"{float(np.abs(logits['jnp']).max()):.3e}")
    check(err <= PREFILL_REL_TOL,
          f"pallas/jnp prefill logits differ: rel L2 {err:.3e}")


def phase_train(arch: str = SMOLLM, full: bool = True, steps: int = 5,
                global_batch: int = 8, seq_len: int = 2048):
    """``steps`` optimizer steps through ``launch/train.main``; each loss
    must be finite.  The checkpoint lives in a temporary directory that is
    removed at the end."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import train
    from repro.models.layers import resolve_attn_impl

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        argv = ["--arch", arch, "--data-axis", "1", "--model-axis", "1",
                "--steps", str(steps), "--global-batch", str(global_batch),
                "--seq-len", str(seq_len), "--ckpt-dir", ckpt,
                "--ckpt-interval", str(10 * steps)]
        if full:
            argv.append("--full")
        trainer, (params, opt, step) = train.main(argv)
        impl = resolve_attn_impl(trainer.cfg)
        hist = trainer.history
        check(len(hist) == steps, f"{len(hist)} of {steps} steps ran")
        for h in hist:
            check(bool(np.isfinite(h["loss"])),
                  f"step {h['step']}: loss {h['loss']}")
            log(f"[train] step {int(h['step'])}: loss {h['loss']:.4f}, "
                f"{h['step_time_s']:.3f}s, attention forward {impl}"
                + (" / backward blockwise jnp (custom VJP)"
                   if impl == "pallas" else ""))
        steady = [h["step_time_s"] for h in hist[1:]]
        cfg = trainer.cfg
        log(f"[train] {cfg.name}: {cfg.n_layers} layers, d_model "
            f"{cfg.d_model}, global batch {global_batch} x {seq_len}; "
            f"first step {hist[0]['step_time_s']:.2f}s (incl. compile), "
            f"median later step {float(np.median(steady)):.3f}s")
        batch = trainer.data.global_batch(step)
        compiled = trainer.bundle.fn.lower(params, opt, batch,
                                           jnp.int32(step)).compile()
        report_memory("train", compiled, "train step")


def run_one_chip(compiles: CompileLog) -> None:
    import jax

    from repro.launch.mesh import make_host_mesh

    srv = phase_serve()
    compiles.report("serve")
    phase_prefill_check(srv.params, make_host_mesh(1, 1))
    compiles.report("prefill")
    del srv
    gc.collect()
    jax.clear_caches()
    phase_train()
    compiles.report("train")


# ---------------------------------------------------------------------------
# four chips: PGAS, conduit collectives, fused matmuls, sharded model
# ---------------------------------------------------------------------------


def phase_pgas(n: int = 4, mat: int = 1024) -> None:
    """The quickstart's ring PUT, an Active Message and the ART matmul on
    an ``n``-rank global address space, each against numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import am, art, pgas

    mesh = jax.make_mesh((n,), ("pgas",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    heap = pgas.SymmetricHeap(64)
    heap.alloc("inbox", 16)
    heap.alloc("result", 16)
    gas = pgas.GlobalAddressSpace(mesh, "pgas", heap)
    inbox, result = heap.addr("inbox"), heap.addr("result")

    def ring_put(h):
        my = jax.lax.axis_index("pgas").astype(jnp.float32)
        return pgas.put(h, jnp.full((16,), my + 1.0), inbox, axis="pgas",
                        perm=[(i, (i + 1) % n) for i in range(n)])

    gj = gas.run(ring_put)(gas.zeros_global())
    g = np.asarray(gj).reshape(n, 64)
    want = np.zeros((n, 64), np.float32)
    for r in range(n):
        want[(r + 1) % n, inbox:inbox + 16] = r + 1
    check(np.array_equal(g, want), "ring PUT landed the wrong words")

    reg = am.HandlerRegistry()

    def scale_handler(h, args, payload):
        box = jax.lax.dynamic_slice(h, (args[0],), (16,))
        h = jax.lax.dynamic_update_slice(h, box * args[1].astype(h.dtype),
                                         (args[2],))
        return h, jnp.int32(0), am.make_args(), jnp.zeros((1,), h.dtype)

    scale = reg.register_request("SCALE", scale_handler)

    def send_compute(h):
        return am.am_request_short(reg, h, scale,
                                   am.make_args(inbox, 10, result),
                                   axis="pgas", perm=[(0, 2)])

    g2 = np.asarray(gas.run(send_compute)(gj)).reshape(n, 64)
    want[2, result:result + 16] = want[2, inbox:inbox + 16] * 10
    check(np.array_equal(g2, want), "AM SCALE handler wrote the wrong words")

    rng = np.random.default_rng(0)
    m = rng.standard_normal((mat, mat)).astype(np.float32)
    w = rng.standard_normal((mat, mat)).astype(np.float32)
    f = jax.jit(jax.shard_map(
        functools.partial(art.art_matmul_reducescatter, axis="pgas",
                          n_chunks=4),
        mesh=mesh, in_specs=(P(None, "pgas"), P("pgas", None)),
        out_specs=P(None, "pgas")))
    with jax.default_matmul_precision("highest"):
        got = f(jax.device_put(m, NamedSharding(mesh, P(None, "pgas"))),
                jax.device_put(w, NamedSharding(mesh, P("pgas", None))))
    err = rel_err(got, m.astype(np.float64) @ w)
    log(f"[pgas] ring PUT and AM SCALE exact on {n} ranks; ART matmul "
        f"({mat}x{mat})@({mat}x{mat}) rel L2 {err:.2e} vs numpy")
    check(err < 1e-4, f"ART matmul rel L2 {err:.2e}")


def phase_conduit(n: int = 4, rows: int = 256, cols: int = 1024) -> None:
    """Every registered transport of the four data collectives against the
    ``lax`` builtin, on an integer-valued f32 payload of ``rows x cols``
    per rank (exact under any summation order)."""
    import jax
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.conduit import Conduit, transports

    mesh = jax.make_mesh((n,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    builtin = {
        "all_gather": lambda x: lax.all_gather(x, "x", axis=0, tiled=True),
        "reduce_scatter": lambda x: lax.psum_scatter(
            x, "x", scatter_dimension=0, tiled=True),
        "all_reduce": lambda x: lax.psum(x, "x"),
        "all_to_all": lambda x: lax.all_to_all(
            x, "x", split_axis=0, concat_axis=0, tiled=True),
    }
    x = np.random.default_rng(1).integers(
        -8, 8, size=(n * rows, cols)).astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("x", None)))
    mib = rows * cols * 4 / (1 << 20)
    for op, ref in builtin.items():
        def run(body):
            return np.asarray(jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P("x", None),
                out_specs=P("x", None), check_vma=False))(xs))

        want = run(ref)
        names = transports(op)
        for t in names:
            got = run(lambda v, t=t: getattr(Conduit("x", transport=t),
                                             op)(v))
            check(np.array_equal(got, want),
                  f"conduit {op}/{t} differs from the lax builtin")
        log(f"[conduit] {op}: {'/'.join(names)} equal lax on {n} ranks, "
            f"{mib:.1f} MiB per rank")


def phase_fused_matmuls(n: int = 4, b_loc: int = 256, k: int = 1024,
                        n_loc: int = 512) -> None:
    """The in-kernel remote-DMA collective matmuls against their refs; the
    compiled program must hold one ring kernel per direction and no
    collective-permute (the emulated fallback's ppermute hops)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.cc_matmul import (allgather_matmul_pallas,
                                         allgather_matmul_ref,
                                         matmul_reducescatter_pallas,
                                         matmul_reducescatter_ref)
    from repro.kernels.cc_matmul.ops import _rows_tpu_ok

    mesh = jax.make_mesh((n,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(2)
    cases = {
        "allgather_matmul": (
            allgather_matmul_pallas, allgather_matmul_ref,
            (n * b_loc, k), (k, n * n_loc), P("x", None), P(None, "x"),
            P(None, "x")),
        "matmul_reducescatter": (
            matmul_reducescatter_pallas, matmul_reducescatter_ref,
            (n * b_loc, n * k), (n * k, n_loc), P(None, "x"), P("x", None),
            P("x", None)),
    }
    for name, (fused, ref, xs, ws, xspec, wspec, ospec) in cases.items():
        x = jax.device_put(
            jnp.asarray(rng.standard_normal(xs), jnp.bfloat16),
            NamedSharding(mesh, xspec))
        w = jax.device_put(
            jnp.asarray(rng.standard_normal(ws), jnp.bfloat16),
            NamedSharding(mesh, wspec))
        want = jax.jit(jax.shard_map(
            functools.partial(ref, axis="x"), mesh=mesh,
            in_specs=(xspec, wspec), out_specs=ospec, check_vma=False))(x, w)
        for bidir in (False, True):
            check(_rows_tpu_ok(b_loc, bidir),
                  f"{name}: {b_loc} rows per rank are not TPU-tileable")
            f = jax.jit(jax.shard_map(
                functools.partial(fused, axis="x", bidirectional=bidir),
                mesh=mesh, in_specs=(xspec, wspec), out_specs=ospec,
                check_vma=False))
            compiled = f.lower(x, w).compile()
            text = compiled.as_text()
            kernels = text.count("tpu_custom_call")
            # one ring kernel per direction; the emulated fallback would
            # show its ppermute hops and one consume kernel per hop
            check(kernels == (2 if bidir else 1)
                  and "collective-permute" not in text,
                  f"{name} bidir={bidir}: {kernels} kernel call(s), "
                  f"collective-permute in program: "
                  f"{'collective-permute' in text} — the remote-DMA ring "
                  f"did not run (emulated fallback)")
            err = rel_err(compiled(x, w), want)
            log(f"[fused] {name} bidirectional={bidir}: remote-DMA ring, "
                f"{kernels} kernel call(s), no collective-permute; rel L2 "
                f"{err:.2e} vs ref")
            check(err < 1e-3, f"{name} bidir={bidir}: rel L2 {err:.2e}")


def phase_sharded_model(arch: str = DANUBE, full: bool = True,
                        batch: int = 2, prompt_len: int = 4608,
                        decode_steps: int = 16, model_axis: int = 4) -> None:
    """Prefill + ``decode_steps`` greedy decode steps of ``arch`` on
    data=1 x model=``model_axis`` against the same model on one chip of
    this process; decode is teacher-forced with the one-chip tokens, so the
    two runs see the same inputs and their logits are compared each step."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.dist.sharding import param_pspecs, to_shardings
    from repro.dist.steps import (StepConfig, build_prefill_step,
                                  build_serve_step)
    from repro.launch.mesh import make_host_mesh
    from repro.models.layers import resolve_attn_impl
    from repro.models.model import init_params

    cfg = get_config(arch)
    if not full:
        cfg = cfg.reduced()
    max_seq = prompt_len + decode_steps
    toks = np.random.default_rng(3).integers(
        0, cfg.vocab_size, size=(batch, prompt_len)).astype(np.int32)
    host_params = None
    runs = {}
    for label, model in (("1 chip", 1), (f"model={model_axis}", model_axis)):
        mesh = make_host_mesh(1, model)
        shape = jax.eval_shape(lambda k: init_params(cfg, k),
                               jax.random.PRNGKey(0))
        psh = to_shardings(mesh, param_pspecs(cfg, mesh, shape))
        if host_params is None:
            params = jax.jit(lambda k: init_params(cfg, k),
                             out_shardings=psh)(jax.random.PRNGKey(0))
            host_params = jax.device_get(params)
        else:
            params = jax.device_put(host_params, psh)
        pre = build_prefill_step(cfg, mesh, StepConfig(), batch=batch,
                                 seq_len=prompt_len, cache_len=max_seq)
        compiled = pre.fn.lower(params, toks).compile()
        kernels = compiled.as_text().count("tpu_custom_call")
        check(kernels > 0, f"{label}: prefill holds no tpu_custom_call")
        cache, logits = compiled(params, toks)
        serve = build_serve_step(cfg, mesh, StepConfig(), batch=batch,
                                 max_seq=max_seq)
        cache = jax.device_put(cache, to_shardings(mesh, serve.in_specs[1]))
        steps = [np.asarray(jax.device_get(logits), np.float32)]
        feed = runs["1 chip"]["ids"] if runs else None
        ids = []
        for i in range(decode_steps):
            nxt = (feed[i] if feed is not None
                   else steps[-1].argmax(-1).astype(np.int32))
            ids.append(nxt)
            cache, logits = serve.fn(params, cache, nxt)
            steps.append(np.asarray(jax.device_get(logits), np.float32))
        runs[label] = {"logits": steps, "ids": ids}
        report_memory(f"sharded:{label}", compiled, "prefill")
        log(f"[sharded] {cfg.name} on {label}: {cfg.n_layers} layers, "
            f"d_model {cfg.d_model}, window {cfg.window}; prefill "
            f"{batch}x{prompt_len} ({resolve_attn_impl(cfg)} attention, "
            f"{kernels} kernel call(s)) + {decode_steps} decode steps")
        del params, cache
    one, tp = runs["1 chip"]["logits"], runs[f"model={model_axis}"]["logits"]
    errs = [rel_err(b, a) for a, b in zip(one, tp)]
    agree = float(np.mean([(a.argmax(-1) == b.argmax(-1)).mean()
                           for a, b in zip(one, tp)]))
    log(f"[sharded] model={model_axis} vs 1 chip: rel L2 prefill "
        f"{errs[0]:.3e}, decode max {max(errs[1:]):.3e} (tol "
        f"{SHARDED_REL_TOL:g}); greedy argmax agreement {agree:.3f}")
    check(all(np.isfinite(v).all() for v in one + tp), "non-finite logits")
    check(max(errs) <= SHARDED_REL_TOL,
          f"sharded logits differ: rel L2 {max(errs):.3e}")


def run_four_chips(compiles: CompileLog) -> None:
    phase_sharded_model()
    compiles.report("sharded")
    phase_pgas()
    compiles.report("pgas")
    phase_conduit()
    compiles.report("conduit")
    phase_fused_matmuls()
    compiles.report("fused")


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: serve, prefill check and train on one chip; "
                        "4: only the cross-chip paths and their references")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[chip] {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); compile cache {enable_compile_cache()}")
    compiles = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(compiles)
    else:
        run_four_chips(compiles)
    log(f"[chip] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
