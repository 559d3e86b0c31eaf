"""prefill_ms: device time of the admission prefill (`Engine._prefill`
over `models/transformer.prefill`) in the traced call, over the admissions
that ran it, in ms: the summed ``jit_prefill`` programs over the count of
the scheduler's ``serve.admit`` spans."""
from bench import trace

# `Engine._prefill` jits a function named `prefill`: a v5e trace names its
# program `jit_prefill(<fingerprint>)`
MODULE = "jit_prefill"
SPAN = "serve.admit"


def read(ctx):
    lo, hi = ctx["window"]
    admits = sum(1 for name, s, d in ctx["trace"]["host"]
                 if name == SPAN and lo <= s and s + d <= hi)
    ns = trace.total_ns(ctx["trace"], trace.MODULES_LINE, ctx["window"],
                        lambda name: name.split("(")[0] == MODULE)
    if not admits or not ns:
        return None
    return ns * 1e-6 / admits
