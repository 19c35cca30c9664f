"""Open-loop serving traffic: arrival times and request sizes from the
cell's parameters (``workloads/<cell>.json``, key ``traffic``).

Every run gets the same work: the same number of requests in each
segment, the same multiset of gaps between arrivals and the same multiset
of prompt and output lengths, all taken at fixed quantiles of the stated
distributions.  Their order is a permutation drawn from
``schedule_seed`` where the cell gives one (a fixed trace, replayed on
every seed), else from the run's seed.  The run's seed always draws the
prompt tokens.

Parameters:

- ``rate_per_s``; ``arrivals``: ``{"process": "poisson"}``, or
  ``{"process": "gamma", "cv": c}`` for bursts (gaps with coefficient of
  variation ``c``; ``c`` = 1 is Poisson);
- ``preroll_s``: seconds of arrivals before the measured window opens, so
  that the window starts in steady state;
- ``prompt``, ``output``: length specs.  ``dist`` is ``lognormal``
  (``median``, ``sigma``), ``uniform``, or ``mixture`` (``parts``: specs
  with a ``share`` each); every spec but a mixture clips to ``min`` and
  ``max`` and may round up to listed lengths (``round_up_to``) or to a
  multiple (``round_up_multiple``).

A request is due at its arrival time; latencies are measured from then,
not from when it was submitted.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List

import numpy as np

from chipbench.traffic import Arrival


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _shares(weights, n: int) -> List[int]:
    """``n`` split by ``weights``, largest remainders first."""
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[:n - out.sum()]:
        out[i] += 1
    return [int(k) for k in out]


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at fixed quantiles of ``spec`` (sorted ascending)."""
    if spec["dist"] == "mixture":
        parts = spec["parts"]
        counts = _shares([p["share"] for p in parts], n)
        return np.sort(np.concatenate(
            [lengths(p, k) for p, k in zip(parts, counts)]))
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        raw = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        raw = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out = np.clip(np.ceil(raw), spec["min"], spec["max"]).astype(np.int64)
    if "round_up_to" in spec:
        buckets = np.asarray(sorted(spec["round_up_to"]))
        out = buckets[np.searchsorted(buckets, out, side="left")]
    elif "round_up_multiple" in spec:
        m = int(spec["round_up_multiple"])
        out = -(-out // m) * m
    return out.astype(np.int64)


def _spec_lengths(spec: Dict) -> List[int]:
    if spec["dist"] == "mixture":
        return sorted({s for p in spec["parts"] for s in _spec_lengths(p)})
    if "round_up_to" in spec:
        return sorted(int(b) for b in spec["round_up_to"])
    if "round_up_multiple" in spec:
        m = int(spec["round_up_multiple"])
        lo = -(-int(spec["min"]) // m) * m
        return list(range(lo, int(spec["max"]) + 1, m))
    return list(range(int(spec["min"]), int(spec["max"]) + 1))


def length_set(params: Dict) -> List[int]:
    """Every prompt length the cell can produce: the shapes to warm up."""
    return _spec_lengths(params["prompt"])


def gaps(params: Dict, n: int, span: float) -> np.ndarray:
    """``n`` gaps at fixed quantiles of the arrival process, scaled to sum
    to ``span`` (sorted ascending)."""
    proc = params.get("arrivals", {"process": "poisson"})
    u = _quantiles(n)
    if proc["process"] == "poisson":
        g = -np.log1p(-u)
    elif proc["process"] == "gamma":
        from scipy.stats import gamma

        shape = 1.0 / float(proc["cv"]) ** 2
        g = gamma.ppf(u, shape)
    else:
        raise ValueError(f"unknown arrival process {proc['process']!r}")
    return g * (span / g.sum())


def generate(params: Dict, seed: int, seconds: float,
             vocab_size: int) -> List[Arrival]:
    """Arrivals of the preroll and the window, in due order."""
    rng = np.random.default_rng(seed)
    sched = (np.random.default_rng(int(params["schedule_seed"]))
             if "schedule_seed" in params else rng)
    rate = float(params["rate_per_s"])
    out: List[Arrival] = []
    t0 = 0.0
    for segment, span in (("preroll", float(params["preroll_s"])),
                          ("window", float(seconds))):
        n = max(1, int(round(rate * span)))
        g = sched.permutation(gaps(params, n, span))
        p_len = sched.permutation(lengths(params["prompt"], n))
        o_len = sched.permutation(lengths(params["output"], n))
        due = t0 + np.cumsum(g)
        for i in range(n):
            toks = rng.integers(0, vocab_size, size=int(p_len[i]),
                                dtype=np.int64).astype(np.int32)
            out.append(Arrival(len(out), segment, float(due[i]), toks,
                               int(o_len[i])))
        t0 += span
    return out


def mean_output(params: Dict, n: int = 1000) -> float:
    return float(lengths(params["output"], n).mean())


def offered_tokens_per_s(params: Dict) -> float:
    return float(params["rate_per_s"]) * mean_output(params)


__all__ = ["generate", "gaps", "lengths", "length_set", "mean_output",
           "offered_tokens_per_s"]
