"""Wait for prefill: from a request's submission to the dispatch of its
bulk prefill (or first chunk), by the program's own ``Request.submitted``
and ``prefill_start`` stamps (host clock), 90th percentile over the
requests due in the window.  It holds the steps a request spends in a slot
waiting its turn for the one prefill a step, and leaves out how late the
harness submitted it.  A program without the stamps reads nothing."""

from chipbench.serve import nearest_rank


def read(record, reduced, peak):
    waits = [r.req.prefill_start - r.req.submitted for r in record["recs"]
             if r.window and getattr(r.req, "prefill_start", None) is not None]
    return 1e3 * nearest_rank(waits, 0.90) if waits else None
