"""Prefill programs' share of the chip's bf16 peak: the model FLOPs the
traced prompts require (attention at causal or windowed need) over the
peak times the device time of the bulk prefill programs."""

from chipbench import flops
from chipbench.metrics import _common as c


def read(record, reduced, peak):
    work = sum(flops.prefill_flops(record["hf"], s)
               for st in c.traced_steps(record) for s in st.prefills)
    t = c.seconds(c.module_events(reduced, c.PREFILL_MODULE))
    return c.share(work, peak["bf16_flops_per_s"] * t)
