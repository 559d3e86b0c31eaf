"""The trace reduction gives known numbers: on a hand-made trace, on a
trace recorded here on the CPU (the loader), and on a trace recorded on a
TPU v5e and kept beside this file."""
from pathlib import Path

import pytest

from bench import trace

DEV = "/device:TPU:0"
# a loop (while.5) holding three ops, then a kernel the window cuts short
HAND = {
    "devices": {DEV: {
        "XLA Ops": [["while.5", 100, 300], ["fusion.1", 100, 50],
                    ["_fused_call.3", 200, 100], ["fusion.2", 300, 50],
                    ["_fused_call.4", 900, 200]],
        "XLA Modules": [["jit_run(7)", 100, 300], ["jit_prefill(3)", 900,
                                                   200]]}},
    "host": [["bench.serve_call", 0, 1000], ["PjitFunction(run)", 40, 30],
             ["bench.make_requests", -50, 40], ["bench.serve_call", 10, 5]],
}
RECORDED = Path(__file__).parent / "v5e_fused_prefill_trace.json.gz"


def test_names_are_shortened_to_the_instruction():
    assert trace.short_name("%fusion.12 = bf16[2]{0} fusion(%a), kind=kLoop"
                            ) == "fusion.12"
    assert trace.short_name("jit_run(123)") == "jit_run(123)"
    assert trace.kind("fusion.12") == "fusion"
    assert trace.kind("_fused_call.405") == "_fused_call"
    assert trace.kind("copy-start") == "copy-start"


def test_window_is_the_longest_span():
    assert trace.window_of(HAND, "bench.serve_call") == (0, 1000)
    with pytest.raises(ValueError):
        trace.window_of(HAND, "nothing")


def test_busy_is_the_union_clipped_to_the_window():
    assert trace.busy_intervals(HAND, (0, 1000))[DEV] == [(100, 400),
                                                          (900, 1000)]
    assert trace.busy_ns(HAND, (0, 1000)) == 300 + 100


def test_totals_match_names():
    assert trace.total_ns(HAND, trace.OPS_LINE, (0, 1000),
                          lambda n: trace.kind(n) == "_fused_call") == 200
    assert trace.total_ns(HAND, trace.MODULES_LINE, (0, 1000),
                          lambda n: n.startswith("jit_run")) == 300
    assert trace.total_ns(HAND, trace.OPS_LINE, (0, 1000),
                          lambda n: False) == 0.0


def test_breakdown_counts_leaves_and_names_idle_time():
    b = trace.breakdown(HAND, (0, 1000))
    ops = dict(b["device_ops"])
    assert "while" not in ops
    assert ops["_fused_call"] == pytest.approx(200e-9)
    assert ops["fusion"] == pytest.approx(100e-9)
    gaps = dict(b["idle_gaps"])
    # gap [0,100) has its middle in the dispatch; [400,900) in the span
    assert gaps["PjitFunction(run)"] == pytest.approx(100e-9)
    assert gaps["bench.serve_call"] == pytest.approx(500e-9)


def test_recorded_v5e_trace_reduces_to_known_numbers():
    """300 ms in the middle of a `fused-decode` call (chip run, TPU v5
    lite): admission prefills, where the megakernel's custom calls take
    most of the device."""
    tr = trace.read(str(RECORDED))
    lo, hi = trace.window_of(tr, "bench.serve_call")
    mid = (lo + hi) // 2
    win = (mid, mid + 300_000_000)
    assert trace.busy_ns(tr, win) == 290_710_098
    kernel = trace.total_ns(tr, trace.OPS_LINE, win,
                            lambda n: trace.kind(n) == "_fused_call")
    assert kernel == 248_896_821
    evs = tr["devices"][DEV][trace.OPS_LINE]
    assert kernel == sum(min(s + d, win[1]) - max(s, win[0])
                         for n, s, d in evs if n.startswith("_fused_call.")
                         and s < win[1] and s + d > win[0])
    assert trace.total_ns(tr, trace.MODULES_LINE, win,
                          lambda n: n.startswith("jit_run(")) == 26_991_422
    b = trace.breakdown(tr, win)
    assert b["device_ops"][0] == ["_fused_call", pytest.approx(0.2488968)]
    assert b["idle_gaps"][0][0] == "$scheduler.py:182 _try_admit"
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        (300_000_000 - 290_710_098) * 1e-9, rel=1e-3)


def test_loader_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.serve_call"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    lo, hi = trace.window_of(tr, "bench.serve_call")
    assert hi > lo
    path = tmp_path / "t.json.gz"
    trace.save(tr, str(path))
    assert trace.read(str(path)) == tr
