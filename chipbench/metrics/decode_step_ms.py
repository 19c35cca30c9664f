"""Device time per call of the batched decode-step program."""

from chipbench.metrics import _common as c


def read(record, reduced, peak):
    ev = c.decode_events(reduced, record)
    if not ev:
        return None
    return 1e3 * c.seconds(ev) / len(ev)
