"""Share of the time inside the harness's ``Server.step()`` spans during
which no operation ran on the device (waits for arrivals left out)."""

from chipbench.metrics import _common as c


def read(record, reduced, peak):
    t = reduced["trace"]
    ops = t.ops[reduced["devices"][0]]
    spans = c.tr.spans(t.host, "bench.server_step")
    inside = sum(h.end - h.start for h in spans)
    busy = sum(c.tr.busy_ns(ops, h.start, h.end) for h in spans)
    return c.share(inside - busy, inside) if inside else None
