"""The flash-attention kernel's share of its roofline in prefill: the
least time its calls need (the larger of FLOPs at causal or windowed need
over peak, and Q, K, V, O bytes over HBM bandwidth), over the summed
device time of the kernel's events inside the prefill programs.  One call
per layer per prompt."""

from chipbench import flops
from chipbench.metrics import _common as c


def read(record, reduced, peak):
    hf = record["hf"]
    least = 0.0
    for st in c.traced_steps(record):
        for s in st.prefills:
            w = flops.flash_call(hf, 1, s)
            least += hf["num_hidden_layers"] * flops.least_time(
                w["flops"], w["bytes"], peak)
    kern = c.in_modules(reduced, c.op_events(reduced, c.FLASH_OP),
                        c.module_events(reduced, c.PREFILL_MODULE))
    return c.share(least, c.seconds(kern))
