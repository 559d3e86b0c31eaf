"""megakernel_roofline: the fused RNS megakernel's (`kernels/
rns_fused.py`) share of its roofline in the traced call, in %.

The least time is the larger of the logical int8 operations over the
chip's int8 peak and the logical bytes over its HBM bandwidth
(`bench.costs.megakernel_work` over the projections the configuration's
reference lists); the time taken is the summed device time of the
kernel's Mosaic events."""
from bench import costs, trace

# the pallas_call carries no name of its own: in a v5e trace its events are
# the custom calls `_fused_call.<n>`, after the wrapper that launches it
KERNEL = "_fused_call"


def read(ctx):
    ns = trace.total_ns(ctx["trace"], trace.OPS_LINE, ctx["window"],
                        lambda name: trace.kind(name) == KERNEL)
    if not ns:
        return None
    work = costs.megakernel_work(ctx["reference"], ctx["model"],
                                 ctx["counters"])
    peaks = ctx["peaks"]
    bound_s, _ = costs.roofline_s(work, peaks["ops_per_s"]["int8"],
                                  peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (ns * 1e-9)
