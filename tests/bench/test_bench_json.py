"""BENCHMARK.json keeps to the benchmark's contract, and every file it
names loads by name, with no change to the harness."""
import json
import re
import shutil

import pytest

from bench import harness, reference, traffic
from bench.harness import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_command_and_paths():
    cmd, paths = SPEC["command"], SPEC["paths"]
    assert 1 <= len(paths) <= 16 and len(cmd) <= 32
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for word in cmd:
        assert LINE.match(word) and not word.startswith("/")
        assert ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths)
            assert (ROOT / word).is_file()


def test_names_and_units():
    named = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
        + SPEC["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"])


def test_entry_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        assert LINE.match(c["source"]) and LINE.match(c["why"])
        assert len(c["reduced"]) <= 16
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])


def test_cells_report_what_they_must():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for w in cells:
        reported = {m["name"] for m in harness.end_to_end_for(SPEC, w)}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.per_layer_for(SPEC, w)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(cells) // 2)


def test_run_seconds_fits_a_full_check():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_by_name(cell):
    spec = harness.resolve_cell(SPEC, cell)
    assert spec["config"]["source"].startswith("https://")
    assert harness.program_config(spec["config"]).num_layers \
        == spec["config"]["num_hidden_layers"]
    limits = spec["limits"]
    compared = [n for n in ("max_logit_gap", "mean_logit_gap") if n in limits]
    assert compared and all(limits[n]["limit"] > 0 for n in compared)
    assert limits["sample_requests"] >= 2
    for m in harness.per_layer_for(SPEC, cell):
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("path", sorted((BENCH / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_traffic_file_loads(path):
    mix = traffic.load(path.stem)
    assert len(traffic.sizes(mix)) == mix["requests_per_call"]
    assert LINE.match(mix["source"]) and LINE.match(mix["why"])


def test_peaks_name_their_source_and_unknown_chips_fail():
    assert harness.peaks_for("TPU v5 lite")["ops_per_s"]["int8"] == 393e12
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9 imaginary")


# A later configuration's reference: the Llama reference with a state size
# of its own, two projections a layer, attention in every fourth layer and
# a state update of 2 · state · d operations a token in every layer.
STAND_IN = """

_llama_shape_of = shape_of


def shape_of(model):
    return dict(_llama_shape_of(model), state=model["mamba_d_state"])


def linears(shape):
    d = shape["d"]
    return [(d, 2 * d), (2 * d, d)] * shape["layers"]


def token_ops(shape, context):
    return (shape["layers"] // 4 * 4 * context * shape["d"]
            + shape["layers"] * 2 * shape["state"] * shape["d"])
"""


def test_a_new_cell_needs_only_files(tmp_path):
    """A later cell — fused-prefill on prefill_heavy — is a workloads entry
    and a limits file: the harness resolves it, and its metrics, by name.
    A later configuration is a config file that names its reference and
    the program sizes it sets, that reference's file, a limits file and
    its configs and workloads entries: it resolves, sets the program's
    sizes, and its per-layer metrics count with its own reference."""
    from bench import trace

    shutil.copytree(BENCH, tmp_path / "bench")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "fused-prefill",
                              "config": "rns-smollm-135m-fused",
                              "traffic": "prefill_heavy", "chips": 1,
                              "why": "long prompts"})
    for m in spec["per_layer"]:
        m["workloads"].append("fused-prefill")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "limits" / "fused-prefill.json").write_text(
        json.dumps({"sample_requests": 4, "max_logit_gap": {"limit": 1.0}}))
    got = harness.resolve_cell(spec, "fused-prefill", tmp_path)
    assert got["mix"]["prompt_len"]["max"] == 496
    assert got["config"]["program"] == "rns-smollm-135m-fused"
    names = [m["name"] for m in harness.per_layer_for(spec, "fused-prefill")]
    assert "megakernel_roofline" in names
    for n in names:
        assert callable(harness.metric_reader(n, tmp_path))

    bench = tmp_path / "bench"
    config = json.loads((bench / "configs"
                         / "rns-smollm-135m-fused.json").read_text())
    config.update(reference="stand_in_reference", mamba_d_state=128,
                  program_keys={"mamba_d_state": "ssm_state"})
    (bench / "configs" / "stand-in.json").write_text(json.dumps(config))
    (bench / "stand_in_reference.py").write_text(
        (bench / "reference.py").read_text() + STAND_IN)
    (bench / "limits" / "stand-in-decode.json").write_text(
        json.dumps({"sample_requests": 4, "max_logit_gap": {"limit": 1.0}}))
    spec["configs"].append({"name": "stand-in", "source": "https://x.y/z",
                            "file": "bench/configs/stand-in.json",
                            "reduced": [], "why": "another architecture"})
    spec["workloads"].append({"name": "stand-in-decode",
                              "config": "stand-in",
                              "traffic": "decode_heavy", "chips": 1,
                              "why": "a state-space mixer"})
    for m in spec["per_layer"]:
        m["workloads"].append("stand-in-decode")
    got = harness.resolve_cell(spec, "stand-in-decode", tmp_path)
    ref = got["reference"]
    assert ref.__file__ == str(bench / "stand_in_reference.py")
    assert harness.program_config(got["config"]).ssm_state == 128

    # one request of a 2-token prompt and one new token, read against the
    # recorded v5e trace of a fused call
    call = harness.Call([traffic.Req([5, 6], 1, 0)], [[5, 6, 7]],
                        {"requests": 1, "new_tokens": 1, "chunks": 0}, 1.0)
    tr = trace.read(str(ROOT / "tests" / "bench"
                        / "v5e_fused_prefill_trace.json.gz"))
    peaks = harness.peaks_for("TPU v5 lite", tmp_path)
    ctx = harness.trace_context(tr, call, got["config"], ref, peaks)
    read = {m["name"]: harness.metric_reader(m["name"], tmp_path)(ctx)
            for m in harness.per_layer_for(spec, "stand-in-decode")}
    # 30 layers of d 576: 2 · 30 · 2 · 576² weight ops a token; attention
    # in 7 layers; 30 · 2 · 128 · 576 state ops a token
    w = 30 * 2 * 2 * 576 ** 2
    head = 2 * 576 * 49152

    def extra(keys):
        return 7 * 4 * keys * 576 + 30 * 2 * 128 * 576

    ops = 2 * 2 * w + head + extra(1) + extra(2)
    assert read["mfu"] == pytest.approx(
        100 * ops / ctx["window_s"] / 393e12)
    # rows: the 2 prompt tokens; one pass; Σ (K + 4·N) = 30 · 5 · 1728
    kernel_s = 1e-9 * trace.total_ns(tr, trace.OPS_LINE, ctx["window"],
                                     lambda n: trace.kind(n) == "_fused_call")
    bound_s = max(2 * 2 * w / 393e12,
                  (w + 2 * 30 * 5 * 1728) / 819e9)
    assert read["megakernel_roofline"] == pytest.approx(
        100 * bound_s / kernel_s)
    llama = dict(ctx, reference=reference, model=reference.shape_of(config))
    assert harness.metric_reader("mfu", tmp_path)(llama) != read["mfu"]
    assert all(v is None or v >= 0 for v in read.values())
