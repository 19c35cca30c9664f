"""Find a serving cell's knee: the highest offered rate that the server
sustains with no growing backlog.  One process builds and warms the
server once, then runs the cell's traffic at each rate in turn.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 2,4,6,8

For each rate it prints the tokens/s completed, TTFT percentiles, the
backlog (queued plus unfinished requests) when the window opened and
closed, the mean number of decoding slots and time per server step in
the window, and the median TTFT of the window's first and last thirds: a
backlog that grows, or a last third far slower than the first, is past
the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    for path in (os.path.join(ROOT, "src"), ROOT):
        sys.path.insert(0, path)
    import jax
    import numpy as np

    from chipbench import program, run, serve, traffic as traffic_mod

    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    wl = run.load_json(HERE, "workloads", args.workload + ".json")
    hf = run.load_json(HERE, "configs", wl["config"] + ".json")
    run.require_chips(int(wl["chips"]))
    cfg = program.model_config(hf)
    mesh = run.make_mesh(wl.get("mesh", {}))
    from chipbench import weights

    t0 = time.perf_counter()
    params = weights.make(hf, args.seed)
    srv = serve._build_server(cfg, params, mesh, wl["server"])
    gen = traffic_mod.load(wl["traffic"])
    serve._warm(srv, gen.length_set(wl["traffic"]), hf["vocab_size"],
                args.seed)
    print(f"[sweep] set-up {time.perf_counter() - t0:.1f}s", flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = dict(wl["traffic"], rate_per_s=rate)
        arrivals = gen.generate(traffic, args.seed, args.seconds,
                                hf["vocab_size"])
        backlog = {}

        class Probe:
            """Reads the backlog as the window opens and closes."""

            def __enter__(self):
                backlog["open"] = len(srv.queue) + sum(
                    s is not None for s in srv.slots)
                backlog["queue_open"] = len(srv.queue)
                return self

            def __exit__(self, *exc):
                backlog["close"] = len(srv.queue) + sum(
                    s is not None for s in srv.slots)
                backlog["queue_close"] = len(srv.queue)
                return False

        recs, steps, t_open, t_close, _, t_end = serve.drive(
            srv, arrivals, float(traffic["preroll_s"]), args.seconds,
            float(traffic["grace_s"]), capture=Probe(),
            trace_seconds=args.seconds)
        for s in list(srv.slots) + list(srv.queue):
            if s is not None:
                srv.cancel(s.rid)
        e2e = serve.end_to_end(recs, t_open, t_close, t_end)
        win = [r for r in recs if r.window]
        ttft = [(r.tok_times[0] - r.due) * 1e3 for r in win if r.tok_times]
        k = max(1, len(ttft) // 3)
        in_win = [st for st in steps if t_open <= st.start < t_close] \
            or steps
        out = {
            "rate_per_s": rate, "requests": len(win),
            "offered_tok_s": gen.offered_tokens_per_s(traffic),
            "serve_tok_s": e2e["serve_tok_s"],
            "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "itl_p95_ms": e2e["itl_p95_ms"],
            "ttft_first_third_ms": float(np.median(ttft[:k])),
            "ttft_last_third_ms": float(np.median(ttft[-k:])),
            "backlog_open": backlog.get("open"),
            "backlog_close": backlog.get("close"),
            "queue_open": backlog.get("queue_open"),
            "queue_close": backlog.get("queue_close"),
            "unfinished": sum(1 for r in win if not r.finished),
            "steps": len(steps),
            "mean_busy_slots": float(np.mean([len(st.decode_positions)
                                              for st in in_win])),
            "mean_step_ms": 1e3 * float(np.mean([st.end - st.start
                                                 for st in in_win])),
            "drain_s": t_end - t_close,
        }
        print("[sweep] " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
