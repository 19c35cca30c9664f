"""Queue wait: from a request's due time to the start of the server step
that took it out of ``Server.queue``, 90th percentile over the requests
due in the window (host clock)."""

from chipbench.serve import nearest_rank


def read(record, reduced, peak):
    waits = [r.admitted - r.due for r in record["recs"]
             if r.window and r.admitted is not None]
    return 1e3 * nearest_rank(waits, 0.90) if waits else None
