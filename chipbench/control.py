"""The readings the correctness limits are set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n,n,...>

For each seed, in this one process: a run of the cell as the benchmark
makes it, at the cell's own rate and sizes (the program's reading of each
compared number), then, on the same requests, the control: the reference
in the program's place, computed with float8 matrix products, the step
below the bf16 the configuration states.  Both readings go through the
same comparison that decides a run's ``correct`` (``serve.checks``,
``run.verdict``), so the control's line has to read ``correct: false``.
One JSON line per seed; the limits in the cell's file are set from these.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    for path in (os.path.join(ROOT, "src"), ROOT):
        sys.path.insert(0, path)
    from chipbench import run, serve

    wl = run.load_json(HERE, "workloads", args.workload + ".json")
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = run.execute(args.workload, seed, args.seconds, False)
        rec = out.pop("_record")
        ctl, _ = serve.checks(wl, rec["hf"], seed, rec["recs"],
                              precision="fp8")
        line = {"seed": seed,
                "program": {"correct": out["correct"], "checks": {
                    k: c["value"] for k, c in out["checks"].items()}},
                "control_fp8": {"correct": run.verdict(ctl), "checks": {
                    k: v for k, (v, _) in ctl.items()}},
                "metrics": {k: m["value"] for k, m in out["metrics"].items()}}
        print("[control] " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
