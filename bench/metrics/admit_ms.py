"""admit_ms: the mean wall time of one admission in the traced call, in
ms: the duration of the scheduler's ``serve.admit`` spans
(`SlotScheduler._try_admit`, from the block reservation through the
prompt's upload, the prefill, the splice and the first token's read)."""
SPAN = "serve.admit"


def read(ctx):
    lo, hi = ctx["window"]
    durs = [d for name, s, d in ctx["trace"]["host"]
            if name == SPAN and lo <= s and s + d <= hi]
    if not durs:
        return None
    return sum(durs) * 1e-6 / len(durs)
