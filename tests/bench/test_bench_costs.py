"""The op and byte arithmetic against hand counts at smollm-135m's shapes."""
import json

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
    assert costs.linear_params(SMOLLM) == 30 * per_layer == 106_168_320


def test_head_and_attention_ops_by_hand():
    assert costs.head_ops(SMOLLM) == 2 * 576 * 49152 == 56_623_104
    # scores and weighted sum: 2 ops · 2 products · keys · 9 heads · 64
    assert costs.attention_ops(SMOLLM, 1) == 30 * 4 * 576 == 69_120
    assert costs.attention_ops(SMOLLM, 100) == 6_912_000


def test_request_ops_by_hand():
    per_token = 2 * 106_168_320
    head = 56_623_104
    prompt = 2 * per_token + head + 69_120 * (1 + 2)
    decode = per_token + head + 69_120 * 3
    assert costs.request_ops(SMOLLM, 2, 2) == prompt + decode == 750_670_848
    # one new token: the prompt's pass alone
    assert costs.request_ops(SMOLLM, 2, 1) == prompt
    assert costs.served_ops(SMOLLM, [2, 2], [2, 1]) == 2 * prompt + decode


def test_megakernel_work_by_hand():
    counters = {"prompt_lens": [40, 60], "new_tokens": 300, "requests": 2,
                "chunks": 20, "decode_chunk": 8}
    w = costs.megakernel_work(SMOLLM, counters)
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
           "model": SMOLLM, "window_s": 0.5,
           "config": {"serving": {"linear_dtype": "int8"}},
           "peaks": peaks["TPU v5 lite"]}
    ops = costs.served_ops(SMOLLM, [2, 2], [2, 1])
    assert metric_reader("mfu")(ctx) == pytest.approx(
        100 * ops / 0.5 / 393e12)


def test_lane_occupancy_reader_by_hand():
    from bench.harness import metric_reader

    ctx = {"counters": {"new_tokens": 13_789, "requests": 64, "chunks": 84,
                        "decode_chunk": 8, "slots": 32}}
    assert metric_reader("lane_occupancy")(ctx) == pytest.approx(
        100 * 13_725 / (84 * 8 * 32))
