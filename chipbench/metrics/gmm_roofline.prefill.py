"""The grouped-matmul kernel's share of its roofline in prefill: the least
time of the dropless MoE's grouped-matmul calls in the traced prefills
(per call, the larger of the routed rows' FLOPs over peak and the held
experts' weights they reach plus the rows' bytes over HBM bandwidth,
both at the expectation of uniform routing; three calls per MoE layer per
prompt), over the summed device time of the kernel's events inside the
prefill programs.  A family without the counts, or a program without the
kernel, reads nothing."""

import re

from chipbench import families, flops
from chipbench.metrics import _common as c

#: the kernel's events (kernels/grouped_matmul: ``moe_gmm``)
GMM_OP = re.compile(r"moe_gmm")


def read(record, reduced, peak):
    hf = record["hf"]
    fam = families.load(hf)
    if not hasattr(fam, "gmm_call"):
        return None
    least = 0.0
    for st in c.traced_steps(record):
        for s in st.prefills:
            w = fam.gmm_call(hf, s)
            least += fam.gmm_calls_per_prompt(hf) * flops.least_time(
                w["flops"], w["bytes"], peak)
    kern = c.in_modules(reduced, c.op_events(reduced, GMM_OP),
                        c.module_events(reduced, c.PREFILL_MODULE))
    return c.share(least, c.seconds(kern))
