"""The program's own spans and stamps, as the benchmark reads them: idle
gaps under nested ``serve.*`` spans, the decode step found by name once
the slot writer has a name of its own, and the two readers of the
``Request`` stamps."""

import importlib.util
import os
import types

import pytest

from chipbench import trace as tr
from chipbench.serve import Rec, StepRec
from chipbench.trace import Event as E

DEV = "/device:TPU:0"
#: two server steps of one device (ns): a prefill, the slot writer and
#: the decode step in the first, the decode step alone in the second
OPS = [E("fusion.1", 20, 50), E("dus.2", 55, 60), E("while.3", 70, 160),
       E("while.3", 215, 300)]
MODS = [E("jit_fwd(11)", 20, 50), E("jit_slot_write(12)", 55, 60),
        E("jit_fn(13)", 70, 160), E("jit_fn(13)", 215, 300)]
BENCH = [E("bench.server_step", 0, 170), E("bench.wait_arrival", 170, 200),
         E("bench.server_step", 200, 310)]
#: the program's spans in those steps: the host dispatches the prefill and
#: the writer, then waits in ``serve.first_token`` while they run
SERVE = [E("serve.step", 5, 168), E("serve.admit", 6, 15),
         E("serve.prefill", 16, 30), E("serve.first_token", 30, 66),
         E("serve.decode", 66, 69), E("serve.fetch", 69, 161),
         E("serve.emit", 161, 167),
         E("serve.step", 202, 309), E("serve.admit", 203, 205),
         E("serve.decode", 206, 213), E("serve.fetch", 213, 301),
         E("serve.emit", 301, 308)]


def _reduced(host):
    trace = tr.Trace(ops={DEV: OPS}, modules={DEV: MODS},
                     host=sorted(host, key=lambda e: e.start))
    return {"trace": trace, "devices": [DEV]}


def _record():
    steps = [StepRec(1.0, 1.17, [128], [300] * 4),
             StepRec(1.2, 1.31, [], [301] * 4)]
    return {"traced": (0.0, 10.0), "steps": steps}


def _load(metric):
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("m_" + metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_gaps_take_the_innermost_program_span():
    """Idle time inside the harness's step spans is labelled by the
    program's span open at its midpoint; gaps outside them keep theirs."""
    bare = dict(tr.gaps_by_label(tr.gaps(OPS, BENCH, 0, 310)))
    assert bare == {"bench.server_step": 45, "bench.wait_arrival": 55}
    full = dict(tr.gaps_by_label(tr.gaps(OPS, BENCH + SERVE, 0, 310)))
    assert full == {"serve.admit": 20, "serve.first_token": 5 + 10,
                    "serve.emit": 10, "bench.wait_arrival": 55}


def test_program_spans_change_no_device_number():
    """``busy``, the step spans' idle share and the decode readers read
    the same with the program's spans in the host list as without."""
    bare, full = _reduced(BENCH), _reduced(BENCH + SERVE)
    idle = _load("device_idle.serve")
    dec = _load("decode_step_ms")
    assert idle(_record(), full, None) == idle(_record(), bare, None) \
        == pytest.approx(100 * 70 / 280)
    assert dec(_record(), full, None) == dec(_record(), bare, None) \
        == pytest.approx(1e3 * 175e-9 / 2)
    for r in (bare, full):
        assert tr.busy_ns(r["trace"].ops[DEV], 0, 310) == 30 + 5 + 90 + 85


def test_decode_step_found_by_name_beside_the_renamed_writer():
    """With the slot writer named ``jit_slot_write`` the decode step is
    the one ``jit_fn``, even in a window where every step admitted."""
    from chipbench.metrics import _common as c

    mods = []
    for i in range(20):
        t = 100 * i
        mods += [E("jit_fwd(1)", t, t + 30),
                 E("jit_slot_write(2)", t + 31, t + 32),
                 E("jit_fn(3)", t + 35, t + 95)]
    reduced = {"trace": tr.Trace(ops={DEV: []}, modules={DEV: mods},
                                 host=[]), "devices": [DEV]}
    steps = [StepRec(1.0 + i, 1.5 + i, [64], [100]) for i in range(20)]
    dec = c.decode_events(reduced, {"traced": (0.0, 100.0), "steps": steps})
    assert {e.name for e in dec} == {"jit_fn(3)"} and len(dec) == 20


def _rec(submitted, prefill_start, first_token, window=True):
    req = types.SimpleNamespace(submitted=submitted,
                                prefill_start=prefill_start,
                                first_token=first_token)
    arrival = types.SimpleNamespace(segment="window" if window else "preroll")
    return Rec(arrival, due=submitted, req=req)


def test_stamp_readers():
    wait, stall = _load("prefill_wait_p90_ms"), _load("prefill_stall_ms")
    recs = [
        _rec(1.00, 1.01, 1.05),     # prefilled in the step it arrived
        # took a slot, then waited a 0.13 s step for its prefill turn
        _rec(1.00, 1.14, 1.17),
        _rec(2.00, 2.02, None),     # prefilled, first token not yet back
        _rec(3.00, None, None),     # still waiting for its prefill
        _rec(0.00, 0.50, 0.90, window=False),   # before the window
    ] + [_rec(4.0 + i, 4.0 + i + 0.01, 4.0 + i + 0.04) for i in range(7)]
    record = {"recs": recs}
    # waits (ms): 10, 140, 20, then 10 x 7; nearest-rank p90 of 10 is the
    # 9th smallest
    assert wait(record, None, None) == pytest.approx(20.0)
    # stalls (ms): 40, 30, then 30 x 7
    assert stall(record, None, None) == pytest.approx((40 + 30 * 8) / 9)


@pytest.mark.parametrize("recs", [
    [],                                             # no request
    [_rec(1.0, None, None), _rec(2.0, None, None)],  # no prefill in window
    [_rec(1.0, 1.2, 1.3, window=False)],            # only before the window
])
def test_stamp_readers_read_nothing_without_a_prefill(recs):
    for metric in ("prefill_wait_p90_ms", "prefill_stall_ms"):
        assert _load(metric)({"recs": recs}, None, None) is None


def test_stamp_readers_read_nothing_from_a_program_without_stamps():
    """A ``Request`` with ``submitted`` and ``first_token`` only."""
    req = types.SimpleNamespace(submitted=1.0, first_token=1.1)
    rec = Rec(types.SimpleNamespace(segment="window"), due=1.0, req=req)
    for metric in ("prefill_wait_p90_ms", "prefill_stall_ms"):
        assert _load(metric)({"recs": [rec]}, None, None) is None
