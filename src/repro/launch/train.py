"""Training launcher: ``python -m repro.launch.train --arch smollm-360m``.

By default it trains the *reduced* config over a CPU host mesh; ``--full``
trains the published widths (on one TPU chip: ``--full --data-axis 1
--model-axis 1``).  ``main(argv)`` runs in-process and returns the
:class:`Trainer` with the final ``(params, opt, step)``.  All
fault-tolerance features (checkpoint/restart, preemption, straggler
watchdog) are live either way.
"""

from __future__ import annotations

import argparse
import os


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="smollm-360m")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--global-batch", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--data-axis", type=int, default=2)
    p.add_argument("--model-axis", type=int, default=2)
    p.add_argument("--expert-axis", type=int, default=1,
                   help="expert mesh axis extent (>1 enables EP dispatch "
                        "for MoE archs when --moe-transport is non-xla)")
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--grad-bucket-kb", type=int, default=0,
                   help="accumulate microbatch grads in size-targeted "
                        "buckets of this many KiB (0: pytree accumulation; "
                        "bit-identical update — DESIGN §3)")
    p.add_argument("--moe-transport", default="xla",
                   help="TransportPolicy.moe: xla|ring|bidir|auto "
                        "(non-xla needs an expert mesh axis)")
    p.add_argument("--moe-stream-chunks", type=int, default=0,
                   help="stream the EP dispatch in this many ART chunks "
                        "(0: bulk exchange)")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-interval", type=int, default=50)
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--full", dest="reduced", action="store_false")
    args = p.parse_args(argv)

    n_dev = args.data_axis * args.model_axis * args.expert_axis
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n_dev}")

    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticLM
    from repro.dist.steps import StepConfig, TransportPolicy
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.trainer import Trainer, TrainerConfig

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(args.data_axis, args.model_axis,
                          args.expert_axis)
    scfg = StepConfig(
        microbatches=args.microbatches, peak_lr=args.lr,
        warmup_steps=max(args.steps // 20, 5), total_steps=args.steps,
        seq_chunk=min(2048, args.seq_len),
        grad_bucket_bytes=(args.grad_bucket_kb << 10) or None,
        transport=TransportPolicy(
            moe=args.moe_transport,
            moe_stream_chunks=args.moe_stream_chunks or None),
    )
    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len + 1,
        global_batch=args.global_batch))
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_interval=args.ckpt_interval)
    trainer = Trainer(cfg, scfg, tcfg, data, mesh=mesh)
    trainer.install_signal_handler()
    params, opt, step = trainer.train()
    print(f"[train] finished at step {step}; "
          f"final loss {trainer.history[-1]['loss']:.4f}")
    return trainer, (params, opt, step)


if __name__ == "__main__":
    main()
