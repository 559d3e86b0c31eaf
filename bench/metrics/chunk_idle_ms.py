"""chunk_idle_ms: the device's idle time inside one decode chunk of the
traced call, in ms: the time in the scheduler's ``serve.chunk`` spans
(`SlotScheduler.serve`'s upload, chunk program, readback and bookkeeping)
in which no operation ran on the device, over the count of those spans,
averaged over the device planes that ran an operation."""
from bench import trace

SPAN = "serve.chunk"


def _covered(busy, spans):
    """Nanoseconds of the sorted, disjoint ``spans`` that the sorted,
    disjoint ``busy`` intervals cover."""
    out, i = 0, 0
    for s, e in spans:
        while i < len(busy) and busy[i][1] <= s:
            i += 1
        j = i
        while j < len(busy) and busy[j][0] < e:
            out += min(e, busy[j][1]) - max(s, busy[j][0])
            j += 1
    return out


def read(ctx):
    lo, hi = ctx["window"]
    spans = sorted((s, s + d) for name, s, d in ctx["trace"]["host"]
                   if name == SPAN and lo <= s and s + d <= hi)
    planes = [iv for iv in trace.busy_intervals(ctx["trace"],
                                                ctx["window"]).values() if iv]
    if not spans or not planes:
        return None
    total = sum(e - s for s, e in spans)
    idle = sum(total - _covered(iv, spans) for iv in planes) / len(planes)
    return idle * 1e-6 / len(spans)
