"""The scheduler's spans and per-request clock, and the three readers that
reduce them to per-layer metrics (``prefill_ms``, ``admit_ms``,
``chunk_idle_ms``): a call served here on the CPU under the profiler, a
hand-made trace with known answers, and a trace recorded on a TPU v5e.

One file, so that one test worker holds the profiler."""
import dataclasses
from pathlib import Path

import jax
import pytest

from bench import harness, trace, traffic
from repro.serve.engine import bucket_plen

import tiny

DEV = "/device:TPU:0"
READERS = ("prefill_ms", "admit_ms", "chunk_idle_ms")
RECORDED = Path(__file__).parent / "v5e_float_spans_trace.json.gz"


def _read(name, tr, win):
    return harness.metric_reader(name)({"trace": tr, "window": win})


def _spans(tr, name):
    return [(s, s + d) for n, s, d in tr["host"] if n == name]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One call of the small float cell under the profiler, its requests
    arriving 3 decode steps apart on 4 slots: later requests wait for the
    clock, and refill slots that retirements free."""
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    spec = harness.resolve_cell(harness.load_benchmark(root), "float-decode",
                                root)
    config, mix = spec["config"], spec["mix"]
    sched = harness.build(config, mix, 5)
    harness.warm(sched, mix, 5)
    reqs = [dataclasses.replace(r, arrival=3.0 * i) for i, r in enumerate(
        harness.program_requests(traffic.call(mix, 5, 0,
                                              config["vocab_size"])))]
    profile = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(profile))
    try:
        outs = sched.serve(reqs)
    finally:
        jax.profiler.stop_trace()
    return {"trace": trace.load(str(profile)), "stats": dict(sched.stats),
            "reqs": reqs, "outs": outs, "cfg": sched.cfg,
            "slots": config["serving"]["slots"]}


def test_one_span_per_call_admission_and_chunk(served):
    tr, stats = served["trace"], served["stats"]
    (call,) = _spans(tr, "serve.call")
    admits, chunks = _spans(tr, "serve.admit"), _spans(tr, "serve.chunk")
    assert len(admits) == stats["requests"] == len(served["reqs"])
    assert len(chunks) == stats["chunks"] > 0
    assert all(call[0] <= s and e <= call[1] for s, e in admits + chunks)


def test_uploads_and_readbacks_lie_inside_an_admission_or_a_chunk(served):
    tr = served["trace"]
    parents = _spans(tr, "serve.admit") + _spans(tr, "serve.chunk")
    children = _spans(tr, "serve.upload") + _spans(tr, "serve.readback")
    # each admission uploads its prompt, slot map and sampling scalars, and
    # reads its first token; each chunk uploads and reads back once
    assert len(children) >= 4 * len(_spans(tr, "serve.admit")) \
        + 2 * len(_spans(tr, "serve.chunk"))
    for s, e in children:
        assert any(ps <= s and e <= pe for ps, pe in parents), (s, e)


def test_per_request_clock_is_ordered(served):
    stats, reqs = served["stats"], served["reqs"]
    clock = stats["per_request"]
    assert len(clock) == len(reqs)
    for c, r in zip(clock, reqs):
        assert 0 <= c["eligible_s"] <= c["seated_s"] <= c["first_s"] \
            <= c["last_s"]
        assert c["new_tokens"] == r.max_new_tokens
    # arrivals are in input order, so eligibility is too, and the last
    # request became eligible only after the first chunk
    assert [c["eligible_s"] for c in clock] == sorted(
        c["eligible_s"] for c in clock)
    assert clock[-1]["eligible_s"] > clock[0]["first_s"]
    lat = sorted(1e3 * (c["last_s"] - c["eligible_s"]) for c in clock)
    assert stats["latency_ms_p50"] == lat[len(lat) // 2]
    assert stats["latency_ms_p99"] == lat[-1]
    assert not any(k.startswith("latency_steps") for k in stats)


def test_prefill_rows_count_every_lane_of_every_bucket(served):
    stats, reqs = served["stats"], served["reqs"]
    assert stats["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    assert stats["prefill_rows"] == sum(
        served["slots"] * bucket_plen(served["cfg"], len(r.prompt))
        for r in reqs)


def test_readers_on_the_call_served_here(served):
    tr = served["trace"]
    win = trace.window_of(tr, "serve.call")
    admits = _spans(tr, "serve.admit")
    assert _read("admit_ms", tr, win) == pytest.approx(
        sum(e - s for s, e in admits) * 1e-6 / len(admits))
    # the CPU has no device plane: the device's readers read nothing
    assert _read("prefill_ms", tr, win) is None
    assert _read("chunk_idle_ms", tr, win) is None


# two admissions and two chunks in the window, one admission after it; an
# op that straddles the chunks' boundary counts in each for its own part
HAND = {
    "devices": {DEV: {
        "XLA Ops": [["fusion.1", 20, 150], ["fusion.2", 230, 60],
                    ["while.3", 360, 100], ["fusion.4", 450, 160],
                    ["copy.5", 600, 60], ["while.3", 650, 280],
                    ["fusion.1", 1100, 40]],
        "XLA Modules": [["jit_prefill(1)", 20, 150],
                        ["jit_prefill(1)", 230, 60], ["jit_run(2)", 360, 300],
                        ["jit_run(2)", 650, 280],
                        ["jit_prefill(1)", 1100, 40]]}},
    "host": [["bench.serve_call", 0, 1000], ["serve.call", 5, 990],
             ["serve.admit", 10, 200], ["serve.upload", 12, 5],
             ["serve.admit", 220, 100], ["serve.chunk", 330, 300],
             ["serve.readback", 470, 150], ["serve.chunk", 640, 300],
             ["serve.admit", 1100, 50]],
}


def test_readers_on_a_hand_made_trace():
    win = trace.window_of(HAND, "bench.serve_call")
    # (150 + 60) ns of prefill over 2 admissions
    assert _read("prefill_ms", HAND, win) == pytest.approx(105e-6)
    # (200 + 100) ns over 2 admissions
    assert _read("admit_ms", HAND, win) == pytest.approx(150e-6)
    # busy 360-930: idle 30 ns of the first chunk (330-360),
    # 10 of the second (930-940)
    assert _read("chunk_idle_ms", HAND, win) == pytest.approx(20e-6)


def test_readers_read_nothing_without_the_spans():
    """The program before these spans existed: every reader is silent."""
    bare = dict(HAND, host=[e for e in HAND["host"]
                            if not e[0].startswith("serve.")])
    win = trace.window_of(bare, "bench.serve_call")
    for name in READERS:
        assert _read(name, bare, win) is None


def test_recorded_v5e_trace_reduces_to_known_numbers():
    """The last two admissions and the first two decode chunks of a traced
    `float-decode` call (chip run, TPU v5 lite), times from the cut's
    start: each admission's prefill, splice and first token, then two
    chunks of 8 decode steps."""
    tr = trace.read(str(RECORDED))
    win = tuple(tr["window"])
    admits, chunks = _spans(tr, "serve.admit"), _spans(tr, "serve.chunk")
    assert len(admits) == 2 and len(chunks) == 2
    assert admits[1][1] < chunks[0][0]
    children = _spans(tr, "serve.upload") + _spans(tr, "serve.readback")
    assert all(any(ps <= s and e <= pe for ps, pe in admits + chunks)
               for s, e in children)
    # two `jit_prefill` programs, 9,387,697 ns
    assert _read("prefill_ms", tr, win) == pytest.approx(4.6938485, rel=1e-9)
    # admissions of 13,126,277 and 13,200,787 ns
    assert _read("admit_ms", tr, win) == pytest.approx(13.163532, rel=1e-9)
    # the chunks' readbacks and uploads: 14,020,773 idle ns in two chunks
    assert _read("chunk_idle_ms", tr, win) == pytest.approx(7.0103865,
                                                           rel=1e-9)
