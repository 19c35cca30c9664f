"""The traffic generators and the serving loop's clock."""

import time

import numpy as np
import pytest

from chipbench import serve, traffic
from chipbench.traffic import Arrival, open_loop

PARAMS = {
    "rate_per_s": 8.0, "preroll_s": 1.0, "grace_s": 5.0,
    "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
               "min": 128, "max": 2048,
               "round_up_to": [128, 256, 512, 1024, 2048]},
    "output": {"dist": "lognormal", "median": 160, "sigma": 0.7,
               "min": 32, "max": 512},
}
SEED = 2**31 + 12345          # more than 31 bits


def test_same_seed_same_arrivals():
    a = open_loop.generate(PARAMS, SEED, 10.0, 1000)
    b = open_loop.generate(PARAMS, SEED, 10.0, 1000)
    assert [(x.due, x.out_len) for x in a] == [(x.due, x.out_len) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_gets_the_same_work_in_another_order():
    a = open_loop.generate(PARAMS, SEED, 10.0, 1000)
    b = open_loop.generate(PARAMS, SEED + 1, 10.0, 1000)
    for seg in ("preroll", "window"):
        sa = [x for x in a if x.segment == seg]
        sb = [x for x in b if x.segment == seg]
        assert len(sa) == len(sb) == round(8.0 * (1.0 if seg == "preroll"
                                                   else 10.0))
        assert sorted(x.prompt.size for x in sa) == sorted(
            x.prompt.size for x in sb)
        assert sorted(x.out_len for x in sa) == sorted(x.out_len for x in sb)
        t0 = 0.0 if seg == "preroll" else 1.0
        assert np.allclose(sorted(np.diff([t0] + [x.due for x in sa])),
                           sorted(np.diff([t0] + [x.due for x in sb])))
    assert [x.prompt.size for x in a] != [x.prompt.size for x in b]


def test_lengths_fall_in_the_cells_shapes():
    a = open_loop.generate(PARAMS, SEED, 10.0, 1000)
    assert {x.prompt.size for x in a} <= set(open_loop.length_set(PARAMS))
    assert all(32 <= x.out_len <= 512 for x in a)
    windows = [x.due for x in a if x.segment == "window"]
    assert 1.0 <= min(windows) and max(windows) <= 11.0


class SlowServer:
    """A stand-in for ``Server``: each step takes ``tick`` seconds and
    gives every admitted request one token; one slot."""

    def __init__(self, tick):
        self.tick, self.queue, self.slots, self.n = tick, [], [None], 0

    class Req:
        def __init__(self, rid):
            self.rid, self.out_tokens, self.phase = rid, [], "queued"
            self.first_token = None

    def submit(self, prompt):
        self.queue.append(self.Req(self.n))
        self.n += 1

    def step(self):
        time.sleep(self.tick)
        if self.slots[0] is None and self.queue:
            self.slots[0] = self.queue.pop(0)
            self.slots[0].phase = "decode"
        r = self.slots[0]
        if r is not None:
            r.out_tokens.append(1)
            if r.first_token is None:
                r.first_token = time.perf_counter()

    def cancel(self, rid):
        if self.slots[0] is not None and self.slots[0].rid == rid:
            self.slots[0].phase = "done"
            self.slots[0] = None


def test_a_fixed_schedule_is_replayed_on_every_seed():
    """With ``schedule_seed`` the times and sizes are the cell's; the run's
    seed draws only the tokens."""
    p = dict(PARAMS, schedule_seed=20231)
    a = open_loop.generate(p, SEED, 10.0, 1000)
    b = open_loop.generate(p, SEED + 1, 10.0, 1000)
    assert [(x.due, x.prompt.size, x.out_len) for x in a] == [
        (x.due, x.prompt.size, x.out_len) for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_mixture_lengths_keep_their_shares():
    spec = {"dist": "mixture", "parts": [
        {"share": 0.8, "dist": "uniform", "min": 64, "max": 256,
         "round_up_multiple": 64},
        {"share": 0.2, "dist": "uniform", "min": 4096, "max": 8192,
         "round_up_multiple": 1024}]}
    got = open_loop.lengths(spec, 50)
    assert (got <= 256).sum() == 40 and (got >= 4096).sum() == 10
    assert open_loop.length_set({"prompt": spec}) == [
        64, 128, 192, 256, 4096, 5120, 6144, 7168, 8192]


def test_gamma_arrivals_are_burstier_at_the_same_rate():
    n, span = 400, 100.0
    poisson = open_loop.gaps({}, n, span)
    bursty = open_loop.gaps({"arrivals": {"process": "gamma", "cv": 3.0}},
                            n, span)
    assert poisson.sum() == pytest.approx(span)
    assert bursty.sum() == pytest.approx(span)
    cv = lambda g: g.std() / g.mean()  # noqa: E731
    assert cv(poisson) == pytest.approx(1.0, abs=0.1)
    assert cv(bursty) > 2.0


#: a generator that the harness has never seen, in a file of its own
FIXED_GENERATOR = """
import numpy as np
from chipbench.traffic import Arrival


def length_set(params):
    return [int(params["prompt_len"])]


def offered_tokens_per_s(params):
    return params["rate_per_s"] * params["out_len"]


def generate(params, seed, seconds, vocab_size):
    rng = np.random.default_rng(seed)
    out = []
    for segment, t0, span in (("preroll", 0.0, params["preroll_s"]),
                              ("window", params["preroll_s"], seconds)):
        n = max(1, int(params["rate_per_s"] * span))
        for i in range(n):
            toks = rng.integers(0, vocab_size, params["prompt_len"])
            out.append(Arrival(len(out), segment, t0 + span * (i + 1) / n,
                               toks.astype(np.int32), params["out_len"]))
    return out
"""


def test_a_new_generator_file_is_found_by_name(tmp_path, monkeypatch):
    """A generator added as a file of its own, and named by a cell, runs a
    whole (tiny, CPU) serving run with no other file changed."""
    from chipbench import run
    from chipbench.tests import tiny

    (tmp_path / "evenly_spaced.py").write_text(FIXED_GENERATOR)
    monkeypatch.setattr(traffic, "DIR", str(tmp_path))
    params = {"generator": "evenly_spaced", "rate_per_s": 6.0,
              "preroll_s": 0.3, "grace_s": 30.0, "prompt_len": 32,
              "out_len": 5}
    gen = traffic.load(params)
    assert gen.length_set(params) == [32]
    ov = tiny.overrides()
    ov["workload"] = dict(ov["workload"], traffic=params)
    out = run.execute("smollm-360m.chat", SEED, 1.0, False,
                      require_chip=False, overrides=ov)
    assert out["correct"], out["checks"]
    recs = out["_record"]["recs"]
    assert {r.arrival.prompt.size for r in recs} == {32}
    assert all(r.finished and len(r.tok_times) == 5 for r in recs)


def test_an_unknown_generator_is_an_error():
    with pytest.raises(FileNotFoundError):
        traffic.load({"generator": "no_such_generator"})
    with pytest.raises(ValueError):
        traffic.load({"generator": "../run"})


def test_latency_counts_from_the_due_time_not_submission():
    """Requests due while the server is busy wait in the queue; their TTFT
    includes that wait, and arrivals keep their schedule meanwhile."""
    arr = [Arrival(i, "window", 0.01 * i,
                             np.zeros(4, np.int32), 3) for i in range(5)]
    srv = SlowServer(tick=0.02)
    recs, steps, t_open, t_close, _, t_end = serve.drive(
        srv, arr, 0.0, 0.1, 5.0)
    assert all(r.finished for r in recs)
    ttft = [r.tok_times[0] - r.due for r in recs]
    # one slot, 3 tokens each at 20 ms: request k waits for k before it
    assert ttft == sorted(ttft)
    assert ttft[-1] > 4 * 3 * 0.02 - 0.05
    # each was submitted no later than a step after it was due
    assert all(abs(r.due - (t_open + r.arrival.due)) < 1e-9 for r in recs)


def test_nearest_rank():
    assert serve.nearest_rank(range(1, 11), 0.9) == 9
    assert serve.nearest_rank([5.0], 0.95) == 5.0


def test_tracing_stall_does_not_eat_the_grace():
    """Stopping a trace blocks the loop; the schedule pauses meanwhile, so
    requests due after it do not count the stall, and the requests due in
    the window still get their full grace after it."""
    class SlowCapture:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            time.sleep(0.3)
            return False

    arr = [Arrival(i, "window", 0.02 * i, np.zeros(4, np.int32), 3)
           for i in range(4)]
    srv = SlowServer(tick=0.005)
    recs, *_ = serve.drive(srv, arr, 0.0, 0.07, 0.2, capture=SlowCapture(),
                           trace_seconds=0.01)
    assert all(r.finished for r in recs)
    late = [r for r in recs if r.arrival.due > 0.02]
    assert late and all(r.tok_times[0] - r.due < 0.2 for r in late)
