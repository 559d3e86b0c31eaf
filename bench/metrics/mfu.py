"""mfu: the whole step's share of the chip's peak, in %.

The operations the served tokens of the traced call need (`bench.costs`:
the configuration's reference counts its projections and each token's
work beyond them, such as attention over the keys before it; the tied
head), over the traced window, over the peak of the configuration's
linear dtype."""
from bench import costs


def read(ctx):
    c = ctx["counters"]
    ops = costs.served_ops(ctx["reference"], ctx["model"], c["prompt_lens"],
                           c["new_lens"])
    peak = ctx["peaks"]["ops_per_s"][ctx["config"]["serving"]["linear_dtype"]]
    return 100.0 * ops / ctx["window_s"] / peak
