"""Fault-tolerant training loop.

Failure model and responses (DESIGN §6):

  device/host loss     -> catch the typed ``RankFailure`` (or take the
                          membership detector's declaration), ``elastic``
                          re-mesh excluding the dead member, rebuild the
                          step on the new mesh, restore the last checkpoint
                          resharded, resume (data pipeline is stateless —
                          nothing else to recover).  Any other exception
                          (a compile error, an OOM) propagates: retrying
                          it would loop
  straggler            -> per-step wall-clock watchdog; a step slower than
                          ``straggler_factor ×`` the trailing median is
                          flagged; after ``straggler_patience`` consecutive
                          flags the offending host set is treated as failed
                          and the elastic path runs (in simulation we log)
  preemption (SIGTERM) -> handler requests a checkpoint at the next step
                          boundary, then exits cleanly
  periodic             -> atomic checkpoint every ``ckpt.interval`` steps
                          (write-temp + fsync + rename; see checkpoint/)

The loop is deliberately synchronous-SPMD (one jit per step): fault
tolerance lives *around* the step, not inside it, exactly like the paper
keeps the host off the FPGA's critical path.
"""

from __future__ import annotations

import dataclasses
import signal
import statistics
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.core.conduit import RankFailure
from repro.data.pipeline import SyntheticLM
from repro.dist.bucketing import DEFAULT_BUCKET_BYTES
from repro.dist.steps import (StepConfig, build_init, build_train_step,
                              refit_step_config)
from repro.runtime.elastic import ElasticRuntime


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 300
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_interval: int = 100
    keep_last: int = 3
    log_interval: int = 10
    straggler_factor: float = 3.0
    straggler_patience: int = 3
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, scfg: StepConfig, tcfg: TrainerConfig,
                 data: SyntheticLM, mesh=None,
                 log_fn: Callable[[str], None] = print,
                 fault_plan=None, membership=None):
        self.cfg, self.scfg, self.tcfg = cfg, scfg, tcfg
        self.data = data
        self.log = log_fn
        self.mesh = mesh
        self.fault_plan = fault_plan
        # live detector path: a MembershipService polled at every host
        # step — its declarations (not scripted raises) drive recovery
        self.membership = membership
        self.elastic: Optional[ElasticRuntime] = None
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, tcfg.ckpt_interval,
                                      tcfg.keep_last)
        self._preempted = False
        self._step_times: List[float] = []
        self._straggler_strikes = 0
        self.history: List[Dict[str, float]] = []

    # -- preemption ----------------------------------------------------------

    def install_signal_handler(self):
        def _handler(signum, frame):
            self._preempted = True
        signal.signal(signal.SIGTERM, _handler)

    # -- build / restore -----------------------------------------------------

    def _build(self, mesh):
        from repro.data.pipeline import batch_specs

        dcfg = self.data.cfg
        bshape = batch_specs(dcfg.seq_len - 1, dcfg.global_batch,
                             dcfg.vocab_size)
        self.bundle = build_train_step(self.cfg, mesh, self.scfg, bshape)
        self.init_fn, (self.pspecs, self.ospecs) = build_init(
            self.cfg, mesh, self.scfg)

    def _state_shardings(self, mesh):
        from repro.dist.sharding import to_shardings
        return (to_shardings(mesh, self.pspecs),
                to_shardings(mesh, self.ospecs))

    def _restore_or_init(self, mesh):
        self._build(mesh)
        psh, osh = self._state_shardings(mesh)
        template = (self.bundle.aux["params_shape"],
                    self.bundle.aux["opt_shape"])
        got = self.ckpt.restore_or_none(template, (psh, osh))
        if got is not None:
            (params, opt), manifest = got
            start = manifest["step"]
            self.log(f"[trainer] restored step {start} from {self.ckpt.directory}")
            return params, opt, start
        params, opt = self.init_fn(jax.random.PRNGKey(self.tcfg.seed))
        return params, opt, 0

    # -- straggler watchdog ---------------------------------------------------

    def _watch_step_time(self, dt: float) -> bool:
        """Returns True when the straggler budget is exhausted."""
        self._step_times.append(dt)
        window = self._step_times[-50:]
        if len(window) < 5:
            return False
        med = statistics.median(window[:-1])
        if dt > self.tcfg.straggler_factor * med:
            self._straggler_strikes += 1
            self.log(f"[watchdog] slow step {dt*1e3:.1f} ms vs median "
                     f"{med*1e3:.1f} ms (strike {self._straggler_strikes})")
        else:
            self._straggler_strikes = 0
        return self._straggler_strikes >= self.tcfg.straggler_patience

    # -- main loop -------------------------------------------------------------

    def train(self, mesh=None, on_step: Optional[Callable] = None):
        mesh = mesh or self.mesh
        assert mesh is not None, "Trainer needs a mesh"
        params, opt, start = self._restore_or_init(mesh)
        step = start
        n_failures = 0

        while step < self.tcfg.total_steps:
            if self.membership is not None:
                ev = self.membership.on_step(step)
                if ev is not None and ev.died:
                    n_failures += 1
                    failure = self.membership.failure_for(ev)
                    self.log(f"[trainer] step {step}: membership epoch "
                             f"{ev.epoch} declared ranks {list(ev.died)} "
                             f"dead; elastic recovery #{n_failures}")
                    mesh = self._recover_mesh(mesh, failure)
                    params, opt, step = self._restore_or_init(mesh)
                    continue
                if ev is not None and ev.joined:
                    self.log(f"[trainer] step {step}: membership epoch "
                             f"{ev.epoch} admitted ranks "
                             f"{list(ev.joined)}; scaling out")
                    mesh = self._scale_out(mesh)
                    params, opt, step = self._restore_or_init(mesh)
                    continue
            batch = self.data.global_batch(step)
            t0 = time.perf_counter()
            try:
                if self.fault_plan is not None:
                    # compiled steps never re-enter the conduit: scripted
                    # kills must be delivered at host-step level too
                    self.fault_plan.on_step(step, "train_step")
                params, opt, metrics = self.bundle.fn(
                    params, opt, batch, jnp.int32(step))
                jax.block_until_ready(metrics["loss"])
            except RankFailure as e:    # a named member died mid-step
                n_failures += 1
                self.log(f"[trainer] step {step} failed ({type(e).__name__}: "
                         f"{e}); elastic recovery #{n_failures}")
                mesh = self._recover_mesh(mesh, e)
                params, opt, step = self._restore_or_init(mesh)
                continue
            dt = time.perf_counter() - t0

            step += 1
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = step
            m["step_time_s"] = dt
            self.history.append(m)
            if on_step:
                on_step(step, m)
            if step % self.tcfg.log_interval == 0:
                self.log(f"[trainer] step {step} loss {m['loss']:.4f} "
                         f"ce {m['ce']:.4f} gnorm {m['grad_norm']:.2f} "
                         f"lr {m['lr']:.2e} {dt*1e3:.0f} ms")

            if self._watch_step_time(dt):
                self.log("[watchdog] straggler budget exhausted — would "
                         "trigger elastic re-mesh on a real deployment")
                self._straggler_strikes = 0

            if self.ckpt.should_save(step) or self._preempted:
                path = self.ckpt.save(step, (params, opt),
                                      extra={"loss": m["loss"]})
                self.log(f"[trainer] checkpoint -> {path}")
                if self._preempted:
                    self.log("[trainer] preemption checkpoint complete; exiting")
                    return params, opt, step

        # final checkpoint
        self.ckpt.save(step, (params, opt),
                       extra={"loss": self.history[-1]["loss"]
                              if self.history else None})
        return params, opt, step

    def _recover_mesh(self, mesh, failure: RankFailure):
        """Rebuild the mesh without the member ``failure`` names.

        The :class:`~repro.runtime.elastic.ElasticRuntime` excludes the
        dead member, re-forms the conduits, and scales grad accumulation
        so the global batch survives the data-axis shrink (the rebuilt
        step bundle picks the new ``microbatches`` up from ``self.scfg``).
        """
        model = mesh.shape.get("model", 1)
        if self.elastic is None:
            self.elastic = ElasticRuntime(
                model=model, axis_names=tuple(mesh.axis_names),
                devices=list(mesh.devices.flat),
                fault_plan=self.fault_plan)
        report = self.elastic.on_failure(
            failure, microbatches=self.scfg.microbatches,
            grad_bucket_bytes=self.scfg.grad_bucket_bytes
            or DEFAULT_BUCKET_BYTES)
        old_data = dict(report.old_shape).get("data", 1)
        new_data = dict(report.new_shape).get("data", 1)
        if new_data != old_data:
            self.log(f"[trainer] data axis {old_data} -> {new_data}: "
                     f"grad accumulation x{old_data // new_data} "
                     f"to hold the global batch")
            self.scfg = refit_step_config(self.scfg, old_data, new_data)
        return self.elastic.mesh()

    def _scale_out(self, mesh, device=None):
        """Admit a joining device and re-expand the data axis.

        The inverse of :meth:`_recover_mesh`: the
        :class:`~repro.runtime.elastic.ElasticRuntime` joins the device
        (the first spare when ``None``), re-forms conduits over the grown
        axis, and grad accumulation *divides* so the global batch stays
        constant.  When no spare device exists (a logical membership
        wider than the host's device pool), the mesh is left unchanged —
        the join is a pool-level event only.
        """
        model = mesh.shape.get("model", 1)
        if self.elastic is None:
            self.elastic = ElasticRuntime(
                model=model, axis_names=tuple(mesh.axis_names),
                devices=list(mesh.devices.flat),
                fault_plan=self.fault_plan)
        try:
            report = self.elastic.on_join(
                device, microbatches=self.scfg.microbatches,
                grad_bucket_bytes=self.scfg.grad_bucket_bytes
                or DEFAULT_BUCKET_BYTES)
        except RuntimeError as e:
            self.log(f"[trainer] scale-out skipped: {e}")
            return self.elastic.mesh()
        old_data = dict(report.old_shape).get("data", 1)
        new_data = dict(report.new_shape).get("data", 1)
        if new_data != old_data:
            self.log(f"[trainer] data axis {old_data} -> {new_data}: "
                     f"grad accumulation /{new_data // old_data} "
                     f"to hold the global batch")
            self.scfg = refit_step_config(self.scfg, old_data, new_data)
        return self.elastic.mesh()
