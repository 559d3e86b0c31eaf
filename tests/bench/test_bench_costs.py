"""The op and byte arithmetic against hand counts at smollm-135m's shapes,
through the Llama configurations' reference, and against a stand-in
reference of another architecture."""
import json
import types

import pytest

from bench import costs, reference
from bench.harness import BENCH

SMOLLM = reference.shape_of(json.loads(
    (BENCH / "configs" / "smollm-135m.json").read_text()))


def test_shape_is_published_smollm():
    assert SMOLLM == {"layers": 30, "d": 576, "heads": 9, "kv_heads": 3,
                      "head_dim": 64, "ff": 1536, "vocab": 49152,
                      "eps": 1e-5, "theta": 10000.0}


def test_linear_params_by_hand():
    # q 576·576, k and v 576·192, o 576·576, gate/up 576·1536, down 1536·576
    per_layer = 2 * 331_776 + 2 * 110_592 + 3 * 884_736
    assert per_layer == 3_538_944
    shapes = reference.linears(SMOLLM)
    assert len(shapes) == 30 * 7
    assert sum(k * n for k, n in shapes) == 30 * per_layer == 106_168_320


def test_head_and_attention_ops_by_hand():
    assert costs.head_ops(SMOLLM) == 2 * 576 * 49152 == 56_623_104
    # scores and weighted sum: 2 ops · 2 products · keys · 9 heads · 64
    assert reference.token_ops(SMOLLM, 1) == 30 * 4 * 576 == 69_120
    assert reference.token_ops(SMOLLM, 100) == 6_912_000


def test_request_ops_by_hand():
    per_token = 2 * 106_168_320
    head = 56_623_104
    prompt = 2 * per_token + head + 69_120 * (1 + 2)
    decode = per_token + head + 69_120 * 3
    assert costs.request_ops(reference, SMOLLM, 2, 2) == prompt + decode \
        == 750_670_848
    # one new token: the prompt's pass alone
    assert costs.request_ops(reference, SMOLLM, 2, 1) == prompt
    assert costs.served_ops(reference, SMOLLM, [2, 2], [2, 1]) \
        == 2 * prompt + decode


def test_megakernel_work_by_hand():
    counters = {"prompt_lens": [40, 60], "new_tokens": 300, "requests": 2,
                "chunks": 20, "decode_chunk": 8}
    w = costs.megakernel_work(reference, SMOLLM, counters)
    rows = 40 + 60 + (300 - 2)
    passes = 2 + 20 * 8
    # Σ (K + 4·N) over one layer's seven projections
    act = (576 + 4 * 576) * 2 + (576 + 4 * 192) * 2 \
        + (576 + 4 * 1536) * 2 + (1536 + 4 * 576)
    assert act == 25_728
    assert w["rows"] == rows and w["passes"] == passes
    assert w["ops"] == 2 * rows * 106_168_320
    assert w["bytes"] == passes * 106_168_320 + rows * 30 * act


@pytest.mark.parametrize("ops,bytes_,bound", [(393e12, 1.0, "compute"),
                                             (1.0, 819e9, "memory")])
def test_roofline_names_its_bound(ops, bytes_, bound):
    t, which = costs.roofline_s({"ops": ops, "bytes": bytes_}, 393e12,
                                819e9)
    assert which == bound and t == pytest.approx(1.0)


def test_mfu_reader_by_hand():
    from bench.harness import metric_reader

    peaks = json.loads((BENCH / "peaks.json").read_text())["chips"]
    ctx = {"counters": {"prompt_lens": [2, 2], "new_lens": [2, 1]},
           "reference": reference, "model": SMOLLM, "window_s": 0.5,
           "config": {"serving": {"linear_dtype": "int8"}},
           "peaks": peaks["TPU v5 lite"]}
    ops = costs.served_ops(reference, SMOLLM, [2, 2], [2, 1])
    assert metric_reader("mfu")(ctx) == pytest.approx(
        100 * ops / 0.5 / 393e12)
    # by hand, as `test_request_ops_by_hand` counts it
    prompt = 4 * 106_168_320 + 56_623_104 + 69_120 * 3
    decode = 2 * 106_168_320 + 56_623_104 + 69_120 * 3
    assert ops == 2 * prompt + decode


def test_lane_occupancy_reader_by_hand():
    from bench.harness import metric_reader

    ctx = {"counters": {"new_tokens": 13_789, "requests": 64, "chunks": 84,
                        "decode_chunk": 8, "slots": 32}}
    assert metric_reader("lane_occupancy")(ctx) == pytest.approx(
        100 * 13_725 / (84 * 8 * 32))


# A stand-in of another architecture: 8 layers of width 64 over a
# vocabulary of 100, of which only every fourth attends; every layer runs
# two projections, and adds a per-token state update of 10 · d operations.
HYBRID = {"layers": 8, "d": 64, "vocab": 100}


def _hybrid_linears(shape):
    return [(shape["d"], 3 * shape["d"]), (shape["d"], shape["d"])] \
        * shape["layers"]


def _hybrid_token_ops(shape, context):
    attending = shape["layers"] // 4
    return attending * 4 * context * shape["d"] \
        + shape["layers"] * 10 * shape["d"]


STAND_IN = types.SimpleNamespace(linears=_hybrid_linears,
                                 token_ops=_hybrid_token_ops)


def test_counts_follow_a_stand_in_reference():
    # per token: 2 · 8 · (64·192 + 64·64) weights; head 2 · 64 · 100;
    # beyond them 2 attending layers · 4 · keys · 64, 8 · 640 state ops
    per_token = 2 * 8 * (12_288 + 4_096)
    assert per_token == 262_144
    head = 12_800

    def extra(keys):
        return 2 * 4 * keys * 64 + 8 * 640

    prompt = 3 * per_token + head + extra(1) + extra(2) + extra(3)
    decode = [per_token + head + extra(3 + j) for j in (1, 2)]
    assert costs.request_ops(STAND_IN, HYBRID, 3, 3) == prompt + sum(decode)
    assert costs.served_ops(STAND_IN, HYBRID, [3, 3], [3, 1]) \
        == 2 * prompt + sum(decode)
    counters = {"prompt_lens": [3, 5], "new_tokens": 20, "requests": 2,
                "chunks": 3, "decode_chunk": 8}
    w = costs.megakernel_work(STAND_IN, HYBRID, counters)
    rows, passes = 3 + 5 + 20 - 2, 2 + 3 * 8
    assert w["ops"] == 2 * rows * 8 * 16_384
    # Σ (K + 4·N): (64 + 4·192) + (64 + 4·64) a layer
    assert w["bytes"] == passes * 8 * 16_384 + rows * 8 * (832 + 320)
