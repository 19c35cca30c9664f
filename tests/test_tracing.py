"""The server's own tracing: host spans inside ``Server.step`` read back
from a profiler trace, the ``Request`` stamps, and the program names the
server's jitted steps carry.

A tiny server runs under ``jax.profiler`` on the CPU; the trace is read
back with ``ProfileData``, as an operator would read one taken on the chip.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.sharding import param_pspecs, to_shardings
from repro.models.decode import init_cache
from repro.models.model import init_params
from repro.models.prefill import prefill_chunk_cuts
from repro.runtime import server as server_mod
from repro.runtime.server import Server, ServerConfig

SPANS = (server_mod.SPAN_SUBMIT, server_mod.SPAN_STEP, server_mod.SPAN_ADMIT,
         server_mod.SPAN_PREFILL, server_mod.SPAN_FIRST_TOKEN,
         server_mod.SPAN_DECODE, server_mod.SPAN_FETCH, server_mod.SPAN_EMIT)
#: spans that run inside ``serve.step``
CHILDREN = SPANS[2:]
#: admission modes: ServerConfig overrides
MODES = {"bulk": dict(prefill_chunk=None), "chunked": dict(prefill_chunk=4)}
PROMPT_LENS = (5, 11, 8)


def _params(cfg, mesh):
    shape = jax.eval_shape(lambda k: init_params(cfg, k),
                           jax.random.PRNGKey(0))
    psh = to_shardings(mesh, param_pspecs(cfg, mesh, shape))
    return jax.jit(lambda k: init_params(cfg, k),
                   out_shardings=psh)(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny(mesh22):
    cfg = get_config("smollm-360m").reduced()
    return cfg, _params(cfg, mesh22), mesh22


def _server(tiny, **kw):
    cfg, params, mesh = tiny
    srv = dict(max_batch=2, max_seq=32, max_new_tokens=3)
    srv.update(kw)
    return Server(cfg, params, mesh, srv=ServerConfig(**srv))


def _prompts(cfg):
    rng = np.random.default_rng(5)
    return [rng.integers(0, cfg.vocab_size, size=n) for n in PROMPT_LENS]


def _read_spans(log_dir):
    """(name, start_ns, end_ns, args) of every ``serve.*`` host span."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module", params=sorted(MODES))
def traced(request, tiny, tmp_path_factory):
    """Three requests (more than the two slots) served to the end under
    the profiler: (mode, server, spans)."""
    mode = request.param
    srv = _server(tiny, **MODES[mode])
    log_dir = str(tmp_path_factory.mktemp(f"trace_{mode}"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for p in _prompts(tiny[0]):
            srv.submit(p)
        srv.run()
    finally:
        jax.profiler.stop_trace()
    return mode, srv, _read_spans(log_dir)


def test_every_span_name_appears(traced):
    _, _, spans = traced
    assert {s[0] for s in spans} == set(SPANS)


def test_children_nest_inside_a_step(traced):
    _, srv, spans = traced
    steps = [s for s in spans if s[0] == server_mod.SPAN_STEP]
    assert len(steps) == srv._ticks
    for name, start, end, _ in spans:
        if name in CHILDREN:
            assert any(st[1] <= start and end <= st[2] for st in steps), name
    # a submit happens outside the steps (between them, in this loop)
    for name, start, end, _ in spans:
        if name == server_mod.SPAN_SUBMIT:
            assert not any(st[1] <= start < st[2] for st in steps)


def test_rid_args_match_the_requests(traced):
    mode, srv, spans = traced
    rids = sorted(r.rid for r in srv.done)
    assert rids == list(range(len(PROMPT_LENS)))

    def rid_list(name):
        return [s[3]["rid"] for s in spans if s[0] == name]

    assert rid_list(server_mod.SPAN_SUBMIT) == rids
    assert sorted(rid_list(server_mod.SPAN_FIRST_TOKEN)) == rids
    # one prefill span per dispatch: one for a bulk prefill, one per chunk;
    # their ``tokens`` sum to the prompt
    for r in srv.done:
        mine = [s[3]["tokens"] for s in spans
                if s[0] == server_mod.SPAN_PREFILL and s[3]["rid"] == r.rid]
        n = r.prompt.size
        want = (1 if mode == "bulk"
                else len(prefill_chunk_cuts(n, chunk_len=srv._eff_chunk)))
        assert len(mine) == want and sum(mine) == n


def test_stamps_in_order(traced):
    _, srv, _ = traced
    assert len(srv.done) == len(PROMPT_LENS)
    for r in srv.done:
        assert r.submitted <= r.admitted <= r.prefill_start \
            <= r.first_token <= r.finished, r.rid
    # the third request waited for a slot
    late = max(srv.done, key=lambda r: r.admitted)
    assert late.admitted > late.submitted


def test_first_token_span_ends_each_admission(traced):
    """``serve.prefill`` through ``serve.first_token`` of one request: the
    stall a decoding row sees from one admission."""
    _, _, spans = traced
    for name, start, end, args in spans:
        if name == server_mod.SPAN_FIRST_TOKEN:
            last = [s for s in spans if s[0] == server_mod.SPAN_PREFILL
                    and s[3]["rid"] == args["rid"]][-1]
            assert last[2] <= start


def test_recovered_request_keeps_its_first_stamps(tiny):
    srv = _server(tiny, max_seq=64, max_new_tokens=6, prefill_chunk=4,
                  paged=True, block_size=4)
    for p in _prompts(tiny[0]):
        srv.submit(p)
    # both slots decoding, so a drained request has every stamp
    for _ in range(20):
        if all(r is not None and r.phase == "decode" for r in srv.slots):
            break
        srv.step()
    assert all(r is not None and r.phase == "decode" for r in srv.slots)
    first = {r.rid: (r.submitted, r.admitted, r.prefill_start,
                     r.first_token) for r in srv.slots}
    assert srv.fail_decode_rank(1, n_ranks=2) >= 1
    srv.run()
    done = {r.rid: r for r in srv.done}
    assert len(done) == len(PROMPT_LENS)
    for rid, stamps in first.items():
        r = done[rid]
        assert (r.submitted, r.admitted, r.prefill_start,
                r.first_token) == stamps


def _module_name(lowered):
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split()[0]


def _sds(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def test_decode_step_is_the_only_jit_fn(tiny):
    """Every program of a paged, chunked server (the widest set) has a
    name of its own: the slot writer is ``jit_slot_write``, the decode
    step keeps ``jit_fn`` and the bulk prefill ``jit_fwd``."""
    cfg, params, mesh = tiny
    srv = _server(tiny, max_seq=32, prefill_chunk=4, paged=True,
                  block_size=4)
    se, i32 = 8, jax.ShapeDtypeStruct((), jnp.int32)
    scratch = jax.eval_shape(srv._scratch_init(se))
    bk, bv, slot_pos, pos = jax.eval_shape(srv._blocks_fn(se), scratch)
    npb = bk.shape[1]
    ids = jax.ShapeDtypeStruct((npb,), jnp.int32)
    cache = _sds(srv.cache)
    ring = jax.eval_shape(lambda: init_cache(cfg, 2, 32))
    names = {
        "decode": srv.bundle.fn.lower(params, cache, _sds(srv._next_tok)),
        "bulk": srv._bulk_fn(se).lower(
            params, jax.ShapeDtypeStruct((1, se), jnp.int32)),
        "chunk": srv._chunk_bundle(se, 0, 4).fn.lower(
            params, scratch, jax.ShapeDtypeStruct((1, 4), jnp.int32)),
        "scratch_init": srv._scratch_init(se).lower(),
        "to_pool": srv._blocks_fn(se).lower(scratch),
        "to_ring": srv._finish_fn(se).lower(scratch),
        "slot_write": srv.writer.fn.lower(
            ring, jax.eval_shape(srv._finish_fn(se), scratch), i32),
        "seed": srv._seed_fn(se, 1).lower(
            scratch, cache, jax.ShapeDtypeStruct((1,), jnp.int32)),
        "block_write": srv._block_writer(npb).fn.lower(
            cache, bk, bv, ids, ids, slot_pos, pos, i32),
        "park": srv._park_fn.lower(cache, i32),
    }
    names = {k: _module_name(v) for k, v in names.items()}
    assert names["decode"] == "jit_fn"
    assert names["bulk"] == "jit_fwd"
    assert names["slot_write"] == "jit_slot_write"
    assert len(set(names.values())) == len(names), names
