"""lane_occupancy: decode lanes that emitted a token, over all lane-steps
the decode chunks ran, in % — from the scheduler's counters of the traced
call: (new_tokens − requests) / (chunks · decode_chunk · slots)."""


def read(ctx):
    c = ctx["counters"]
    lane_steps = c["chunks"] * c["decode_chunk"] * c["slots"]
    if not lane_steps:
        return None
    return 100.0 * (c["new_tokens"] - c["requests"]) / lane_steps
