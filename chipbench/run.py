"""Run one cell of the benchmark once, in this process, on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json`` and
``chipbench/workloads/<cell>.json``, its configuration in
``chipbench/configs/<config>.json``, its traffic generator in
``chipbench/traffic/<generator>.py``, the code for its kind of cell in
``chipbench/<kind>.py``, and each per-layer metric's reader in
``chipbench/metrics/<metric>.py``.  The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number the correctness check compared, beside its limit).  The same
numbers are the last lines of standard error.

The run refuses to measure anything but a TPU: with no accelerator, or
fewer chips than the cell asks for, it exits non-zero and prints no
result.  ``setup_s`` runs from the start of this process to the opening
of the measured window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: compiled programs are kept here, at a fixed path inside the checkout,
#: unless JAX_COMPILATION_CACHE_DIR names another directory
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: seconds of the window that a ``--trace 1`` run traces
TRACE_SECONDS = 5.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    pass


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Compiles:
    """Counts backend compiles from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def reset(self):
        self.count = 0
        self.seconds = 0.0


class Context:
    """What a cell's run function gets: the cell, its configuration, the seed,
    the mesh, and the hooks for spans, the trace and notes."""

    def __init__(self, name: str, workload: Dict, hf: Dict, seed: int,
                 seconds: float, mesh, program_cfg, trace_dir: Optional[str]):
        import jax

        self.name, self.workload, self.hf = name, workload, hf
        self.seed, self.seconds, self.mesh = seed, seconds, mesh
        self.program_cfg = program_cfg
        self.trace_dir = trace_dir
        self.trace_seconds = TRACE_SECONDS
        self.compiles = Compiles()
        self.notes: Dict[str, object] = {}
        self.setup_end: Optional[float] = None
        self.window_compiles = 0
        self.memory_peak_bytes = 0
        self.capture = None
        if trace_dir is not None:
            from chipbench.trace import Capture

            self.capture = Capture(trace_dir)
        self._annotation = (jax.profiler.TraceAnnotation
                            if trace_dir is not None else None)

    def annotate(self, name: str):
        if self._annotation is None:
            return contextlib.nullcontext()
        return self._annotation(name)

    def note(self, key: str, value) -> None:
        self.notes[key] = value
        print(f"[chipbench] {key}: {value}", file=sys.stderr, flush=True)

    def read_memory(self) -> None:
        peak = 0
        for d in self.mesh.devices.flat:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = peak


def require_chips(chips: int):
    """Exit non-zero, before anything is measured, unless JAX finds at
    least ``chips`` TPU devices and runs the Pallas kernels compiled."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU: JAX found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise NoChip(f"chipbench: the cell asks for {chips} chips, JAX "
                     f"found {len(devices)}")
    from repro.kernels.common import INTERPRET_ENV, should_interpret

    if should_interpret():
        raise NoChip(f"chipbench: {INTERPRET_ENV} forces the Pallas "
                     f"interpreter")
    return devices


def make_mesh(spec: Dict):
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(int(spec.get("data", 1)), int(spec.get("model", 1)))


def load_reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_kind(kind: str):
    """The module that runs cells of ``kind`` (the cell file's ``kind``):
    ``chipbench/<kind>.py``, with a ``run(ctx)``."""
    if not kind.isidentifier():
        raise ValueError(f"bad cell kind {kind!r}")
    return importlib.import_module("chipbench." + kind)


def verdict(checks: Dict) -> bool:
    """``correct``: every compared number finite and within its limit."""
    return all(math.isfinite(v) and v <= lim for v, lim in checks.values())


def cell_metrics(bench: Dict, cell: str):
    """The cell's end-to-end and per-layer metric entries."""
    def applies(m):
        return cell in m.get("workloads", [cell])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    per = [m for m in bench["per_layer"] if applies(m)]
    return e2e, per


def execute(name: str, seed: int, seconds: float, trace: bool,
            require_chip: bool = True,
            overrides: Optional[Dict] = None) -> Dict:
    """One run of cell ``name``.  ``overrides`` (tests only) replaces parts
    of the workload and configuration files, and ``require_chip=False``
    lets the tests drive the rest of a run on the CPU."""
    bench = load_json(ROOT, "BENCHMARK.json")
    workload = load_json(HERE, "workloads", name + ".json")
    hf = load_json(HERE, "configs", workload["config"] + ".json")
    overrides = overrides or {}
    workload = {**workload, **overrides.get("workload", {})}
    hf = {**hf, **overrides.get("config", {})}

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = (require_chips(int(workload["chips"])) if require_chip
               else jax.devices())

    from chipbench import program

    cfg = program.model_config(hf)
    program.check_param_shapes(cfg, hf)
    mesh = make_mesh(workload.get("mesh", {}))
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    ctx = Context(name, workload, hf, seed, seconds, mesh, cfg, trace_dir)
    for k, v in program.describe(cfg).items():
        ctx.note(k, v)
    out = load_kind(workload["kind"]).run(ctx)
    setup_s = ctx.setup_end - T_START
    ctx.note("compiles_in_window", ctx.window_compiles)
    e2e_entries, per_entries = cell_metrics(bench, name)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(mesh.devices.flat),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if not trace:
        vals = dict(out["end_to_end"], setup_s=setup_s)
        for m in e2e_entries:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    else:
        from chipbench import trace as tr

        try:
            reduced = tr.reduce_run(trace_dir, out["record"])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        t = reduced["trace"]
        ops = t.ops[reduced["devices"][0]]
        mods = t.modules.get(reduced["devices"][0], [])
        ctx.note("trace_programs", [
            (prog, sec, sum(1 for m in mods if m.name == prog))
            for prog, sec in tr.top_ops(mods, 12)])
        from chipbench.metrics import _common

        ctx.note("traced_steps", [
            len(tr.spans(t.host, "bench.server_step")),
            len(_common.traced_steps(out["record"]))])

        kern = [e for e in ops if _common.FLASH_OP.search(e.name)]
        ctx.note("trace_kernel_events", [
            len(kern), tr.top_ops(kern, 4),
            kern[0].name[:600] if kern else None])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]
        from chipbench import flops

        peak = flops.peaks(dev.device_kind if require_chip
                           else "TPU v5 lite")
        for m in per_entries:
            v = load_reader(m["name"])(out["record"], reduced, peak)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = out["checks"]
    result = {"correct": verdict(checks), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    result["_record"] = out["record"]
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chipbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except NoChip as e:
        print(str(e.code), file=sys.stderr)
        return 3
    result.pop("_record")
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
