"""Shared pieces of the metric readers: which device events belong to
which program or kernel, and the serving steps inside the traced part of
the window."""

import collections
import re

from chipbench import trace as tr

#: the jitted bulk prefill program (dist/steps.build_prefill_step)
PREFILL_MODULE = re.compile(r"^jit_fwd\(")
#: the jitted batched decode step (dist/steps.build_serve_step)
DECODE_MODULE = re.compile(r"^jit_fn\(")
#: the Pallas flash-attention kernel's operations
FLASH_OP = re.compile(r"attn_kernel|flash", re.IGNORECASE)


def traced_steps(record):
    t0, t1 = record["traced"]
    return [s for s in record["steps"] if s.start >= t0 and s.end <= t1]


def module_events(reduced, rx):
    dev = reduced["devices"][0]
    return [e for e in reduced["trace"].modules.get(dev, []) if rx.search(e.name)]


def op_events(reduced, rx):
    dev = reduced["devices"][0]
    return [e for e in reduced["trace"].ops.get(dev, []) if rx.search(e.name)]


def seconds(events):
    return sum(e.end - e.start for e in events) / 1e9


def in_modules(reduced, ops, modules):
    """The ``ops`` that ran inside one of the ``modules`` events."""
    iv = tr.union((m.start, m.end) for m in modules)
    out, j = [], 0
    for e in sorted(ops, key=lambda e: e.start):
        while j < len(iv) and iv[j][1] < e.start:
            j += 1
        if j < len(iv) and iv[j][0] <= e.start <= iv[j][1]:
            out.append(e)
    return out


def share(num, den):
    """A percentage, or None where there is nothing to read."""
    if not num or not den:
        return None
    return 100.0 * num / den


def decode_events(reduced, record):
    """Device events of the decode-step program, or ``[]`` where it cannot
    be told apart.  The slot writer is a program of the same name
    (``jit_fn``, another id) that runs once after each bulk prefill; the
    decode step runs once in every server step that decoded.  So the
    decode step is the one ``jit_fn`` program whose events number the
    traced steps that decoded (within the two steps the trace may cut at
    its ends).  Where no program, or more than one, has that count, the
    program's steps have changed: the readers then return nothing rather
    than a number read from the wrong program."""
    by_program = collections.defaultdict(list)
    for m in module_events(reduced, DECODE_MODULE):
        by_program[m.name].append(m)
    decoded = sum(1 for st in traced_steps(record) if st.decode_positions)
    fits = [ev for ev in by_program.values() if abs(len(ev) - decoded) <= 2]
    return fits[0] if decoded and len(fits) == 1 else []
