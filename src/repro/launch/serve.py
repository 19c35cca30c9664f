"""Serving launcher: ``python -m repro.launch.serve --arch smollm-360m``.

Continuous batching with chunked streamed prefill.  By default it serves
the reduced config over a CPU host mesh; ``--full`` serves the published
widths (on one TPU chip: ``--full --data-axis 1 --model-axis 1``).
``main(argv)`` runs in-process and returns the :class:`Server`, so a
caller that holds the chip drives it without a child process.
``--prefill-chunk 0`` falls back to bulk per-slot admission (the
head-of-line-blocking baseline the chunked scheduler exists to kill);
``--expert-axis`` + ``--moe-transport`` route MoE decode through the
expert-parallel conduit dispatch (``docs/serving.md``).
"""

from __future__ import annotations

import argparse
import os

import numpy as np


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="smollm-360m")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--max-new", type=int, default=24)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=256,
                   help="per-slot cache extent (prompt + new tokens)")
    p.add_argument("--data-axis", type=int, default=2)
    p.add_argument("--model-axis", type=int, default=2)
    p.add_argument("--expert-axis", type=int, default=1,
                   help="EP decode: expert mesh-axis extent (MoE archs)")
    p.add_argument("--moe-transport", default="xla",
                   help="TransportPolicy.moe for EP decode "
                        "(xla|ring|bidir|auto)")
    p.add_argument("--prefill-chunk", type=int, default=8,
                   help="tokens per admitted prefill chunk (0: bulk "
                        "per-slot admission)")
    p.add_argument("--arrive-every", type=int, default=0,
                   help="synthetic arrivals: submit one request every N "
                        "scheduler steps (0: all upfront)")
    p.add_argument("--paged", action="store_true",
                   help="paged KV block pool + prefix cache (token-"
                        "identical to the contiguous cache)")
    p.add_argument("--block-size", type=int, default=16,
                   help="KV positions per pool block (--paged only); must "
                        "divide the ring extent and, for prefix caching, "
                        "be a multiple of --prefill-chunk")
    p.add_argument("--dump-tokens", default=None, metavar="PATH",
                   help="write {rid: out_tokens} JSON (CI diffs paged vs "
                        "contiguous runs)")
    p.add_argument("--fail-at-step", type=int, default=None, metavar="N",
                   help="fault injection: kill a decode rank at scheduler "
                        "step N (requires --paged; the server drains and "
                        "re-admits — tokens stay identical to an unfailed "
                        "run)")
    p.add_argument("--fail-rank", type=int, default=1, metavar="R",
                   help="which decode rank dies at --fail-at-step "
                        "(pool-partition index over the data axis)")
    p.add_argument("--chaos-seed", type=int, default=None, metavar="N",
                   help="live-detector churn: a fixed-seed plan (two "
                        "decode ranks lose their lease in one window, an "
                        "AM-delay burst jitters heartbeats, one victim "
                        "later rejoins) delivered through the membership "
                        "detector — NOT scripted raises (requires "
                        "--paged; tokens stay identical to an unfailed "
                        "run)")
    p.add_argument("--full", action="store_true",
                   help="serve the published widths instead of the "
                        "reduced config")
    args = p.parse_args(argv)

    n_dev = args.data_axis * args.model_axis * args.expert_axis
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n_dev}")

    import jax
    from repro.configs import get_config
    from repro.dist.sharding import param_pspecs, to_shardings
    from repro.dist.steps import StepConfig, TransportPolicy
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import init_params
    from repro.runtime.server import Server, ServerConfig, drive_arrivals

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    mesh = make_host_mesh(args.data_axis, args.model_axis, args.expert_axis)
    params_shape = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    psh = to_shardings(mesh, param_pspecs(cfg, mesh, params_shape))
    params = jax.jit(lambda k: init_params(cfg, k), out_shardings=psh)(
        jax.random.PRNGKey(0))

    scfg = StepConfig(transport=TransportPolicy(moe=args.moe_transport))
    plan = None
    membership = None
    if args.fail_at_step is not None:
        assert args.paged, "--fail-at-step needs --paged (the pool " \
            "partition is what a decode rank owns)"
        from repro.runtime.faults import FaultPlan
        plan = FaultPlan.from_cli(args.fail_at_step, args.fail_rank)
    if args.chaos_seed is not None:
        assert args.paged, "--chaos-seed needs --paged (the pool " \
            "partition is what a decode rank owns)"
        assert plan is None, "--chaos-seed and --fail-at-step are " \
            "mutually exclusive chaos drivers"
        from repro.runtime.faults import FaultPlan
        from repro.runtime.membership import LeaseConfig, MembershipService
        crng = np.random.default_rng(args.chaos_seed)
        n_pool = 4                       # logical decode-pool ranks
        kill_at = int(crng.integers(4, 9))
        victims = sorted(crng.choice(np.arange(1, n_pool), size=2,
                                     replace=False).tolist())
        lease = LeaseConfig(lease_period=1, k_misses=3, step_time_s=1e-3)
        # the delay burst (2 lease periods of jitter) stays under K=3
        # misses — the detector must NOT declare anyone for it
        plan = (FaultPlan(deliver="lease")
                .delay_am(2 * lease.step_time_s, at_step=2)
                .kill_rank(victims[0], at_step=kill_at)
                .kill_rank(victims[1], at_step=kill_at))
        membership = MembershipService(n_pool, lease, fault_plan=plan)
        membership.schedule_join(victims[0], at_step=kill_at + 10)
    srv = Server(cfg, params, mesh, scfg=scfg, srv=ServerConfig(
        max_batch=args.max_batch, max_seq=args.max_seq,
        max_new_tokens=args.max_new,
        prefill_chunk=args.prefill_chunk or None,
        paged=args.paged, block_size=args.block_size), fault_plan=plan,
        membership=membership)
    rng = np.random.default_rng(0)
    plen = args.prompt_len
    if cfg.family == "encdec":
        plen = min(plen, cfg.decoder_max_seq)
    prompts = [rng.integers(0, cfg.vocab_size, size=plen)
               for _ in range(args.requests)]
    if cfg.frontend:
        # multimodal archs: synthetic per-request frontend embeds (the
        # vision/audio tower output the server carries through admission)
        prompts = [
            (pr, rng.standard_normal(
                (cfg.frontend_tokens, cfg.frontend_dim), dtype=np.float32))
            for pr in prompts]

    if args.arrive_every:
        steps = drive_arrivals(srv, prompts, args.arrive_every)
    else:
        for pr in prompts:
            srv.submit(*pr) if isinstance(pr, tuple) else srv.submit(pr)
        steps = srv.run()
    if membership is not None:
        # idle-tick until the scheduled rejoin lands (requests may all
        # finish first; the detector keeps running on the step clock)
        extra = 0
        while not any(ev.joined for ev in membership.events) and extra < 200:
            srv.step()
            extra += 1
        steps += extra

    stats = srv.stats()
    mode = str(stats["admission_mode"])
    if args.paged:
        mode += f"+paged(blk{args.block_size})"
    print(f"[serve:{mode}] {stats['requests']} requests, "
          f"{stats['tokens']} tokens in {steps} steps; "
          f"{stats['throughput_tok_s']:.1f} tok/s, "
          f"mean latency {stats['mean_latency_s']*1e3:.1f} ms, "
          f"ttft {stats['mean_ttft_s']*1e3:.1f} ms, "
          f"itl {stats['mean_itl_s']*1e3:.2f} ms")
    if args.paged:
        print(f"[serve:{mode}] prefix hits {stats['prefix_hits']:.0f} / "
              f"misses {stats['prefix_misses']:.0f}, "
              f"pool evictions {stats['pool_evictions']:.0f}, "
              f"free blocks {stats['pool_free_blocks']:.0f}")
    if membership is not None:
        srv.pool.check_conservation()
        deaths = [ev for ev in membership.events if ev.died]
        joins = [ev for ev in membership.events if ev.joined]
        assert len(deaths) == 1 and deaths[0].died == tuple(victims), \
            (deaths, victims)           # double loss = exactly one bump
        assert len(joins) == 1, joins
        print(f"[serve:{mode}] chaos seed {args.chaos_seed}: leases of "
              f"ranks {victims} suppressed at step {kill_at}, detector "
              f"declared both at step {deaths[0].step} (one epoch bump), "
              f"rank {victims[0]} rejoined at step {joins[0].step}; "
              f"epoch {membership.epoch}, "
              f"{stats['recoveries']:.0f} slots drained/re-admitted, "
              f"{stats['reprefilled_tokens']:.0f} tokens re-prefilled, "
              f"{stats['quarantined_blocks']:.0f} blocks quarantined "
              f"(conservation holds)")
    elif plan is not None:
        srv.pool.check_conservation()
        print(f"[serve:{mode}] fault injected at step {args.fail_at_step} "
              f"(rank {args.fail_rank}): {stats['recoveries']:.0f} slots "
              f"drained/re-admitted, "
              f"{stats['reprefilled_tokens']:.0f} tokens re-prefilled, "
              f"{stats['lost_blocks']:.0f} blocks lost "
              f"(conservation holds)")
    if args.dump_tokens:
        import json
        with open(args.dump_tokens, "w") as f:
            json.dump({str(r.rid): r.out_tokens for r in srv.done}, f,
                      sort_keys=True)
    return srv


if __name__ == "__main__":
    main()
