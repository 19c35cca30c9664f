"""Profiler capture and the reduction from a trace to numbers.

``Capture`` wraps ``jax.profiler`` around part of a run (the Python tracer
off, so host work is seen only through the harness's own
``TraceAnnotation`` spans).  ``load`` reads the ``.xplane.pb`` file into
plain tuples, and the functions below reduce those:

- ``union`` / ``busy_ns``: the union of the intervals in which an
  operation ran on a device;
- ``time_by_name``: summed device time of the events whose name matches;
- ``exposed_ns``: time inside matching (collective) events during which no
  other operation ran on that device;
- ``gaps``: the idle intervals of a device, each labelled with the host
  span that was open at its midpoint.

The reduction works on ``Event`` tuples, so the tests can feed it a small
recorded trace without a profiler.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

#: the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: the line that holds one event per executed program
MODULES_LINE = "XLA Modules"
#: names of collective operations in an HLO program
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|collectivepermute|alltoall",
    re.IGNORECASE)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns, on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    """Per device: its ops and its programs; and the host's spans."""
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: List[Event]


def load(log_dir: str, span_prefix: str = "bench.") -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Event(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        Event(e.name, e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        host.append(Event(e.name, e.start_ns, e.end_ns))
    return Trace(ops, modules, sorted(host, key=lambda e: e.start))


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] during which at least one event ran."""
    return total(clip(union((e.start, e.end) for e in events), lo, hi))


def intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def time_by_name(events: Sequence[Event], pattern: str) -> Tuple[float, int]:
    """(summed duration, count) of the events whose name matches."""
    rx = re.compile(pattern)
    hits = [e for e in events if rx.search(e.name)]
    return float(sum(e.end - e.start for e in hits)), len(hits)


def exposed_ns(events: Sequence[Event],
               rx: "re.Pattern" = COLLECTIVE) -> Tuple[float, float]:
    """(time in matching events, the part of it with no other op running)."""
    coll = union((e.start, e.end) for e in events if rx.search(e.name))
    other = union((e.start, e.end) for e in events if not rx.search(e.name))
    hidden = total(intersect(coll, other))
    all_t = total(coll)
    return all_t, all_t - hidden


def gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
         hi: float) -> List[Tuple[str, float]]:
    """Idle intervals of a device within [lo, hi], longest first, each
    labelled with the innermost host span open at its midpoint."""
    busy = clip(union((e.start, e.end) for e in events), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        open_spans = [h for h in host if h.start <= mid <= h.end]
        label = (max(open_spans, key=lambda h: h.start).name
                 if open_spans else "no host span")
        out.append((label, e - s))
    return sorted(out, key=lambda g: -g[1])


def gaps_by_label(gap_list) -> List[Tuple[str, float]]:
    acc: Dict[str, float] = collections.defaultdict(float)
    for label, d in gap_list:
        acc[label] += d
    return sorted(acc.items(), key=lambda kv: -kv[1])


_SUFFIX = re.compile(r"[.:]\d+$")


def short_name(name: str) -> str:
    """An op event's name is its HLO instruction text on the TPU; keep the
    instruction's name (``%copy.110 = ...`` -> ``copy.110``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(events: Sequence[Event], n: int = 10) -> List[Tuple[str, float]]:
    """Device time per operation name (numeric suffixes folded), largest
    first, in ns.  A ``while`` op spans the ops of its body, which are
    counted again under their own names."""
    acc: Dict[str, float] = collections.defaultdict(float)
    for e in events:
        acc[_SUFFIX.sub("", short_name(e.name))] += e.end - e.start
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def spans(host: Sequence[Event], name: str) -> List[Event]:
    return [h for h in host if h.name == name]


class Capture:
    """``with Capture(dir):`` traces the block with the Python tracer off."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False


def reduce_run(log_dir: str, record: Dict) -> Dict:
    """The traced part of a run, reduced: the window on the trace's clock
    (from the first to the last harness span), device busy time averaged
    over the chips, and the breakdown the result line carries."""
    t = load(log_dir)
    devices = sorted(t.ops)
    if not devices or not any(t.ops[d] for d in devices):
        raise RuntimeError(f"the trace under {log_dir} holds no device op")
    if t.host:
        lo = min(h.start for h in t.host)
        hi = max(h.end for h in t.host)
    else:
        lo = min(e.start for d in devices for e in t.ops[d])
        hi = max(e.end for d in devices for e in t.ops[d])
    busy = [busy_ns(t.ops[d], lo, hi) for d in devices]
    first = devices[0]
    gap_list = gaps(t.ops[first], t.host, lo, hi)
    return {
        "trace": t, "devices": devices, "lo": lo, "hi": hi,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "breakdown": {
            "device_ops": [[n, d / 1e9] for n, d in top_ops(t.ops[first])],
            "idle_gaps": [[n, d / 1e9] for n, d in
                          gaps_by_label(gap_list)[:10]],
        },
    }
