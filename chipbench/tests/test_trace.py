"""The reduction from a trace to numbers, on a small recorded trace."""

import pytest

from chipbench import trace as tr
from chipbench.trace import Event as E

#: one device's ops (ns): a matmul, an all-gather that half overlaps a
#: fusion, the flash kernel twice, and idle time between
OPS = [
    E("convolution.1", 0, 100),
    E("all-gather.3", 100, 200),
    E("fusion.7", 150, 180),
    E("_attn_kernel", 300, 350),
    E("_attn_kernel", 360, 400),
    E("all-reduce.2", 500, 540),
]
HOST = [
    E("bench.server_step", 0, 420),
    E("bench.wait_arrival", 420, 480),
    E("bench.server_step", 480, 600),
]


def test_busy_union_merges_overlaps_and_clips():
    assert tr.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    # 0-200, 300-350, 360-400, 500-540 -> 200 + 50 + 40 + 40
    assert tr.busy_ns(OPS, 0, 600) == 330
    assert tr.busy_ns(OPS, 50, 320) == 150 + 20


def test_kernel_time_by_name():
    t, n = tr.time_by_name(OPS, r"attn_kernel")
    assert (t, n) == (90, 2)


def test_exposed_collective_time():
    total, exposed = tr.exposed_ns(OPS)
    # all-gather 100 ns, 30 of it hidden under the fusion; all-reduce 40
    assert total == 140
    assert exposed == 110


def test_gaps_are_labelled_by_the_host_span_open_at_their_midpoint():
    g = tr.gaps(OPS, HOST, 0, 600)
    assert g[0] == ("bench.server_step", 100)        # 200-300
    by = dict(tr.gaps_by_label(g))
    # 200-300 and 350-360 and 400-420.. split by spans at midpoints
    assert by["bench.wait_arrival"] == 100            # 400-500, mid 450
    assert by["bench.server_step"] == 100 + 10 + 60   # + 540-600
    assert sum(d for _, d in g) == 600 - 330


def test_top_ops_fold_numeric_suffixes():
    top = dict(tr.top_ops(OPS + [E("fusion.9", 600, 610)]))
    assert top["fusion"] == 40
    assert top["_attn_kernel"] == 90


def test_a_trace_with_no_device_op_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.load(str(tmp_path))


def _serving_trace(mods):
    host = [E("bench.server_step", 0, 110), E("bench.server_step", 120, 200)]
    trace = tr.Trace(ops={"/device:TPU:0": []},
                     modules={"/device:TPU:0": mods}, host=host)
    return {"trace": trace, "devices": ["/device:TPU:0"]}


def _serving_record(n_decoding_steps, n_steps=None):
    from chipbench.serve import StepRec

    n_steps = n_decoding_steps if n_steps is None else n_steps
    steps = [StepRec(1.0 + i, 1.5 + i, [], [100] if i < n_decoding_steps
                     else []) for i in range(n_steps)]
    return {"traced": (0.0, 100.0), "steps": steps}


def _steps(n, t0=0):
    """``n`` server steps of a trace: the decode step in each, a prefill
    and the slot writer in every fifth."""
    mods = []
    for i in range(n):
        t = t0 + 100 * i
        if i % 5 == 0:
            mods += [E("jit_fwd(9366623401587839256)", t, t + 30),
                     E("jit_fn(2534412736941948919)", t + 31, t + 32)]
        mods.append(E("jit_fn(4289589034432262994)", t + 35, t + 95))
    return mods


def test_serving_readers_on_program_names_seen_on_the_chip():
    """Module names as a v5e trace gives them: the slot writer and the
    decode step are both ``jit_fn`` (two ids); the decode step is the one
    that runs in every step that decoded."""
    from chipbench.metrics import _common as c

    reduced = _serving_trace(_steps(20))
    dec = c.decode_events(reduced, _serving_record(20))
    assert len(dec) == 20
    assert {e.name for e in dec} == {"jit_fn(4289589034432262994)"}
    assert len(c.module_events(reduced, c.PREFILL_MODULE)) == 4


@pytest.mark.parametrize("mods,n_decoding", [
    # every step prefilled: the writer runs as often as the decode step
    ([E("jit_fn(1)", 100 * i, 100 * i + 1) for i in range(20)]
     + [E("jit_fn(2)", 100 * i + 5, 100 * i + 60) for i in range(20)], 20),
    # the decode step split into two programs, or renamed away
    ([E("jit_fn(1)", 100 * i, 100 * i + 30) for i in range(20)]
     + [E("jit_fn(2)", 100 * i + 35, 100 * i + 60) for i in range(20)], 20),
    (_steps(20), 30),
])
def test_decode_step_not_told_apart_reads_nothing(mods, n_decoding):
    """Where the decode step cannot be told apart from other programs of
    its name, the decode readers return nothing, not a wrong number."""
    from chipbench.metrics import _common as c

    assert c.decode_events(_serving_trace(mods),
                           _serving_record(n_decoding)) == []
