"""A configuration chooses the reference that decides its ``correct`` and
the program sizes it sets, by files alone: at the small CPU size of
`tiny`, a configuration made of new files (its config file, its reference,
its limits file, and its configs and workloads entries) is checked
against its own reference; a reference that is not there, or a size the
program has no field for, is refused."""
import json
import time

import pytest

from bench import harness

import tiny

# the planted change: a reference without rotary positions (halving the
# logits would halve every gap, which no limit can catch)
ROPE = 'q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])'


def add_config(root, name: str, source: str) -> str:
    """The small float cell's configuration as a new one, ``name``, that
    names the reference ``bench/<name>_reference.py`` holding ``source``,
    with a cell ``<name>-decode`` of its own; returns the cell's name."""
    bench = root / "bench"
    config = json.loads((bench / "configs" / "smollm-135m.json").read_text())
    module = name.replace("-", "_") + "_reference"
    config["reference"] = module
    (bench / "configs" / f"{name}.json").write_text(json.dumps(config))
    (bench / f"{module}.py").write_text(source)
    cell = f"{name}-decode"
    (bench / "limits" / f"{cell}.json").write_text(
        (bench / "limits" / "float-decode.json").read_text())
    spec = harness.load_benchmark(root)
    spec["configs"].append({"name": name, "source": "https://x.y/z",
                            "file": f"bench/configs/{name}.json",
                            "reduced": [], "why": "a reference of its own"})
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": "small", "chips": 1,
                              "why": "a reference of its own"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    source = (harness.BENCH / "reference.py").read_text()
    assert source.count(ROPE) == 1
    add_config(root, "planted", source.replace(ROPE, "q, k = q, k"))
    add_config(root, "copied", source)
    return root


def test_a_planted_reference_fails_the_check(root):
    r = harness.run("planted-decode", 2 ** 31 + 5, 0.2, False,
                    time.perf_counter(), root=root, require_tpu=False,
                    log=lambda m: None)
    assert r["failed"] == 0 and not r["correct"]
    got = r["check"]["mean_logit_gap"]
    assert got["value"] > got["limit"]


def test_a_copied_reference_reads_as_the_default(root):
    bench = harness.load_benchmark(root)
    copied = harness.resolve_cell(bench, "copied-decode", root)
    default = harness.resolve_cell(bench, "float-decode", root)
    assert copied["reference"].__file__ == str(
        root / "bench" / "copied_reference.py")
    assert default["reference"].__file__ == str(
        root / "bench" / "reference.py")
    config, mix, limits = default["config"], default["mix"], \
        default["limits"]
    sched = harness.build(config, mix, 7)
    harness.warm(sched, mix, 7)
    calls = harness.window(sched, mix, 7, 0.0)["calls"]
    del sched
    want = harness.check(default["reference"], config, limits, 7, calls,
                         mix["slot_tokens"])
    got = harness.check(copied["reference"], copied["config"], limits, 7,
                        calls, mix["slot_tokens"])
    assert harness.passed(want) and got == want


def test_a_missing_reference_is_refused(tmp_path):
    root = tiny.make_root(tmp_path)
    path = root / "bench" / "configs" / "smollm-135m.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    reference="no_such_reference")))
    with pytest.raises(harness.BenchError,
                       match="bench/no_such_reference.py"):
        harness.resolve_cell(harness.load_benchmark(root), "float-decode",
                             root)


def _config():
    path = harness.BENCH / "configs" / "smollm-135m.json"
    return dict(json.loads(path.read_text()), mamba_d_state=32,
                mamba_d_head=16)


def test_program_keys_reach_the_program():
    config = dict(_config(), program_keys={"mamba_d_state": "ssm_state",
                                           "mamba_d_head": "ssm_head_dim"})
    cfg = harness.program_config(config)
    assert (cfg.ssm_state, cfg.ssm_head_dim) == (32, 16)
    # the nine Llama keys still apply
    assert (cfg.num_layers, cfg.d_model) == (30, 576)


@pytest.mark.parametrize("keys,said", [
    ({"mamba_d_state": "no_such_field"}, "which ModelConfig has not"),
    ({"mamba_n_groups": "ssm_state"}, "does not give")])
def test_program_keys_are_refused_where_they_reach_nothing(keys, said):
    with pytest.raises(harness.BenchError, match=said):
        harness.program_config(dict(_config(), program_keys=keys))
