"""The serving cells: open-loop arrivals into the program's ``Server``.

One run: make the weights from the seed, build the server at the cell's
sizes, warm every prompt length of the cell and the decode step, run the
arrival process through the preroll, then measure for ``seconds``.
Arrivals follow the wall clock: a request is submitted at its due time
whatever the server is doing, and every latency counts from the due time
(in a traced run the clock pauses while the profiler starts and stops).
After the window closes no new request arrives, and the harness steps the
server until every request due in the window has all its tokens (or a
minute has passed).  Then a sample of the finished requests is checked
against the plain reference.

The harness stamps each token after the ``Server.step()`` that produced
it (the first token with the program's own ``first_token`` stamp, which is
taken after the id reached the host), and ends a request at its drawn
output length with ``Server.cancel``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import reference, traffic as traffic_mod, weights

WARM_TOKENS = 3


@dataclasses.dataclass
class Rec:
    arrival: traffic_mod.Arrival
    due: float                      # perf_counter seconds
    req: object = None              # the program's Request
    admitted: Optional[float] = None
    tok_times: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None

    @property
    def window(self) -> bool:
        return self.arrival.segment == "window"

    @property
    def finished(self) -> bool:
        return len(self.tok_times) >= self.arrival.out_len


@dataclasses.dataclass
class StepRec:
    start: float
    end: float
    prefills: List[int]             # prompt lengths prefilled in the step
    decode_positions: List[int]     # cache positions of the decoding rows


def _build_server(cfg, params, mesh, server_cfg: Dict):
    from repro.runtime.server import Server, ServerConfig

    srv_cfg = ServerConfig(max_batch=int(server_cfg["max_batch"]),
                           max_seq=int(server_cfg["max_seq"]),
                           max_new_tokens=int(server_cfg["max_new_tokens"]))
    return Server(cfg, params, mesh, srv=srv_cfg)


def _warm(srv, lengths: List[int], vocab: int, seed: int) -> None:
    """Prefill one prompt of every length and decode a few tokens: every
    program the window drives compiles here."""
    rng = np.random.default_rng([seed, 7])
    for s in lengths:
        srv.submit(rng.integers(0, vocab, size=s).astype(np.int32))
        req = srv.queue[-1]
        while len(req.out_tokens) < WARM_TOKENS:
            srv.step()
        srv.cancel(req.rid)


def drive(srv, arrivals, window_start: float, seconds: float, grace: float,
          annotate=None, capture=None, trace_seconds: float = 0.0):
    """Run the open-loop schedule; the window opens ``window_start``
    seconds after the traffic clock starts.  Returns (recs, steps, t_open,
    t_close, traced (t0, t1) or None, run end)."""
    ann = annotate or (lambda name: contextlib.nullcontext())
    t_base = time.perf_counter() + 0.05
    t_open = t_base + window_start
    t_close = t_open + seconds
    pending = collections.deque(sorted(arrivals, key=lambda a: a.due))
    recs: List[Rec] = []
    live: Dict[int, Rec] = {}
    steps: List[StepRec] = []
    traced = None
    tracing = False
    while True:
        now = time.perf_counter()
        # starting and stopping the profiler blocks this loop (stopping it
        # for tens of seconds on the chip): the schedule and the window's
        # close pause meanwhile, so the rest of a traced run sees the same
        # load as an untraced one
        if capture is not None and not tracing and traced is None \
                and now >= t_open:
            capture.__enter__()
            tracing, traced = True, (now, None)
            paused = time.perf_counter() - now
            t_base, t_close = t_base + paused, t_close + paused
        if tracing and now >= min(t_open + trace_seconds, t_close):
            capture.__exit__(None, None, None)
            tracing, traced = False, (traced[0], now)
            paused = time.perf_counter() - now
            t_base, t_close = t_base + paused, t_close + paused
        while pending and t_base + pending[0].due <= now:
            a = pending.popleft()
            with ann("bench.submit"):
                srv.submit(a.prompt)
            rec = Rec(a, t_base + a.due, req=srv.queue[-1])
            recs.append(rec)
            live[rec.req.rid] = rec
        window_left = [r for r in recs if r.window and not r.finished]
        if now >= t_close and not pending and (
                not window_left or now >= t_close + grace):
            break
        if not srv.queue and not any(s is not None for s in srv.slots):
            nxt = (t_base + pending[0].due) if pending else t_close
            with ann("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.01)))
            continue
        before = {rid: (len(r.req.out_tokens), r.req.phase)
                  for rid, r in live.items()}
        t0 = time.perf_counter()
        with ann("bench.server_step"):
            srv.step()
        t1 = time.perf_counter()
        prefills, positions = [], []
        for rid, rec in list(live.items()):
            req = rec.req
            n_old, phase_old = before.get(rid, (0, "queued"))
            if rec.admitted is None and req.phase != "queued":
                rec.admitted = t0
            n_new = len(req.out_tokens)
            for j in range(n_old, n_new):
                rec.tok_times.append(req.first_token if j == 0 else t1)
            if n_old == 0 and n_new > 0:
                prefills.append(int(rec.arrival.prompt.size))
            if phase_old == "decode" or n_new - n_old > (n_old == 0):
                positions.append(int(rec.arrival.prompt.size) + n_new - 1)
            if len(rec.tok_times) >= rec.arrival.out_len:
                rec.done = t1
                if req.phase != "done":
                    srv.cancel(rid)
                del live[rid]
        steps.append(StepRec(t0, t1, prefills, positions))
    if tracing:
        capture.__exit__(None, None, None)
        traced = (traced[0], time.perf_counter())
    return recs, steps, t_open, t_close, traced, time.perf_counter()


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (no interpolation)."""
    v = sorted(values)
    if not v:
        return float("nan")
    return float(v[min(len(v) - 1, max(0, int(np.ceil(q * len(v))) - 1))])


def end_to_end(recs: List[Rec], t_open: float, t_close: float,
               t_end: float) -> Dict[str, float]:
    win = [r for r in recs if r.window]
    # a request that never got its first token counts with the longest
    # wait it could have had: until the run ended
    ttft = [((r.tok_times[0] if r.tok_times else t_end) - r.due)
            for r in win]
    itl = []
    n_tok = 0
    for r in recs:
        ts = r.tok_times
        n_tok += sum(1 for t in ts if t_open <= t < t_close)
        itl += [b - a for a, b in zip(ts, ts[1:]) if t_open <= b < t_close]
    return {"ttft_p50_ms": 1e3 * nearest_rank(ttft, 0.50),
            "ttft_p90_ms": 1e3 * nearest_rank(ttft, 0.90),
            "itl_p95_ms": 1e3 * nearest_rank(itl, 0.95),
            "serve_tok_s": n_tok / (t_close - t_open),
            "_ttft_n": len(ttft), "_itl_n": len(itl)}


def check(hf: Dict, seed: int, recs: List[Rec], n_sample: int,
          pad_to: int, precision: str = "f32") -> Dict[str, float]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over a sample of the finished window requests drawn
    from the seed, the longest among them.  With ``precision`` other than
    f32 it reads the control instead: the gap of the token that the
    reference at that precision puts first, on the same tokens."""
    done = [r for r in recs if r.window and r.finished]
    if not done:
        return {"served_logit_gap": float("inf"), "checked_tokens": 0}
    longest = max(range(len(done)), key=lambda i: (
        done[i].arrival.prompt.size + done[i].arrival.out_len))
    rng = np.random.default_rng([seed, 11])
    others = [i for i in range(len(done)) if i != longest]
    pick = [longest] + list(rng.choice(others, size=min(len(others),
                                                        n_sample - 1),
                                       replace=False))
    params = reference.to_f32(weights.make(hf, seed))
    worst, n = 0.0, 0
    fn = (reference.served_gaps if precision == "f32"
          else reference.control_gaps)
    for i in pick:
        r = done[i]
        served = np.asarray(r.req.out_tokens[:r.arrival.out_len], np.int32)
        g = fn(hf, params, r.arrival.prompt, served, pad_to,
               precision=precision)
        worst = max(worst, float(g.max()))
        n += g.size
    del params
    return {"served_logit_gap": worst, "checked_tokens": n}


def checks(wl: Dict, hf: Dict, seed: int, recs: List[Rec],
           precision: str = "f32") -> Dict:
    """Each number ``correct`` compares, as (value, limit), and the tokens
    checked: the program's served tokens (or, with another ``precision``,
    the control's) and the window's unfinished requests."""
    got = check(hf, seed, recs, int(wl["check"]["requests"]),
                int(wl["server"]["max_seq"]), precision)
    unfinished = sum(1 for r in recs if r.window and not r.finished)
    return {"served_logit_gap": (got["served_logit_gap"],
                                 float(wl["check"]["served_logit_gap"])),
            "unfinished_requests": (float(unfinished), 0.0)}, \
        got["checked_tokens"]


def run(ctx) -> Dict:
    """One run of a serving cell; ``ctx`` is ``run.Context``."""
    import jax

    hf, wl = ctx.hf, ctx.workload
    params_t = wl["traffic"]
    gen = traffic_mod.load(params_t)
    cfg = ctx.program_cfg
    params = weights.make(hf, ctx.seed)
    srv = _build_server(cfg, params, ctx.mesh, wl["server"])
    ctx.note("admission", "chunked" if srv.chunked_admission else "bulk")
    ctx.note("kv_cache", "paged" if srv.srv.paged else "contiguous")
    _warm(srv, gen.length_set(params_t), hf["vocab_size"], ctx.seed)
    jax.block_until_ready(srv.cache)
    arrivals = gen.generate(params_t, ctx.seed, ctx.seconds,
                            hf["vocab_size"])
    ctx.compiles.reset()
    recs, steps, t_open, t_close, traced, t_end = drive(
        srv, arrivals, float(params_t["preroll_s"]), ctx.seconds,
        float(params_t["grace_s"]),
        annotate=ctx.annotate, capture=ctx.capture,
        trace_seconds=ctx.trace_seconds)
    ctx.setup_end = t_open
    ctx.window_compiles = ctx.compiles.count
    ctx.read_memory()
    e2e = end_to_end(recs, t_open, t_close, t_end)
    ctx.note("ttft_p90_ms", e2e["ttft_p90_ms"])
    in_win = [st for st in steps if t_open <= st.start < t_close]
    if len(in_win) > 1:
        # host stalls of the loop, to tell a slow run's cause apart
        ctx.note("longest_step_ms", 1e3 * max(st.end - st.start
                                              for st in in_win))
        ctx.note("longest_between_steps_ms", 1e3 * max(
            b.start - a.end for a, b in zip(in_win, in_win[1:])))
    win = [r for r in recs if r.window]
    unfinished = sum(1 for r in win if not r.finished)
    record = {"kind": "serve", "hf": hf, "recs": recs, "steps": steps,
              "traced": traced}
    del srv, params
    gc.collect()
    got, n_checked = checks(wl, hf, ctx.seed, recs)
    ctx.note("checked_tokens", n_checked)
    return {"end_to_end": e2e, "record": record, "checks": got,
            "attempted": len(win), "failed": unfinished}
