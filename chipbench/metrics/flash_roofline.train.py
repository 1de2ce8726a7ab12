"""Flash attention forward and backward kernels' share of their roofline:
the least time the chip needs for the calls the trace shows (operations
over the bf16 peak or bytes over HBM bandwidth, whichever is larger, per
call, from the shapes by chipbench/flops.py) over the kernels' summed
device time, in percent.

The kernels are the ``flash_attention`` custom calls.  A forward returns
the output and its logsumexp column (``f32[..., 1]``); every other call
is backward work, counted once per call that returns dq (the fused
kernel's three results, or the split dq kernel's one) while the split
dk/dv kernel adds time only."""
import re

from chipbench import flops
from chipbench.trace import DEVICE_PLANE, op_name, outputs

FLASH = re.compile(r"^%flash_attention[.\d]*$")
LSE = re.compile(r",1\]")


def read(rec, ctx):
    ev = ctx.trace_events
    if not ev:
        return None
    t_f = t_b = 0.0
    n_f = n_b = 0
    for e in ev:
        if not (DEVICE_PLANE.match(e["plane"]) and FLASH.match(op_name(e["name"]))):
            continue
        outs = outputs(e["name"])
        if len(outs) == 2 and LSE.search(outs[1]):
            t_f, n_f = t_f + e["dur_ns"], n_f + 1
        else:
            t_b += e["dur_ns"]
            n_b += len(outs) in (1, 3)
    if not (n_f or n_b):
        return None
    a, pk = rec["attn"], ctx.peak
    shape = (a["b"], a["h"], a["kh"], a["s"], a["hd"], a["itemsize"])
    least = (n_f * flops.least_time(*flops.flash_fwd(*shape), pk["flops_bf16"],
                                    pk["hbm_bytes_per_s"])
             + n_b * flops.least_time(*flops.flash_bwd(*shape),
                                      pk["flops_bf16"], pk["hbm_bytes_per_s"]))
    return 100.0 * least / ((t_f + t_b) / 1e9)
