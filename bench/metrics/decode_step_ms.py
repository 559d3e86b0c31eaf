"""decode_step_ms: device time of the scheduler's decode-chunk program
(`serve/scheduler.py`'s chunk function over `models/transformer.
decode_step`) in the traced call, over the decode steps it ran
(chunks · decode_chunk), in ms."""
from bench import trace

# the chunk function is jitted as `run`: a v5e trace names its program
# `jit_run(<fingerprint>)`
MODULE = "jit_run"


def read(ctx):
    c = ctx["counters"]
    steps = c["chunks"] * c["decode_chunk"]
    ns = trace.total_ns(ctx["trace"], trace.MODULES_LINE, ctx["window"],
                        lambda name: name.split("(")[0] == MODULE)
    if not steps or not ns:
        return None
    return ns * 1e-6 / steps
