"""Decode step's share of HBM bandwidth: the least bytes the traced decode
steps must move (the weights, each decoding row's live K/V capped at the
window, one new K/V row per row) over 819 GB/s times the steps' device
time."""

from chipbench import flops
from chipbench.metrics import _common as c


def read(record, reduced, peak):
    hf = record["hf"]
    need = sum(flops.decode_bytes(hf, st.decode_positions)
               for st in c.traced_steps(record) if st.decode_positions)
    t = c.seconds(c.decode_events(reduced, record))
    return c.share(need, peak["hbm_bytes_per_s"] * t)
