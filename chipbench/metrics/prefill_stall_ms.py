"""Admission stall: from the dispatch of a request's bulk prefill to its
first token on the host (the program's ``Request.prefill_start`` and
``first_token`` stamps, host clock), mean over the requests due in the
window.  In bulk admission that is the prefill program, the slot write,
the first-token argmax and the host round trip: how long the decoding
rows wait for one admission.  A program without the stamps reads
nothing."""


def read(record, reduced, peak):
    stalls = [r.req.first_token - r.req.prefill_start for r in record["recs"]
              if r.window and getattr(r.req, "prefill_start", None) is not None
              and r.req.first_token is not None]
    return 1e3 * sum(stalls) / len(stalls) if stalls else None
