"""A whole run of each cell on the CPU at a small size: everything but the
look for a chip, from the weights to the result line."""
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.harness import ROOT

import tiny

CELLS = ("float-decode", "fused-decode")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
def test_run_is_correct_and_reports_its_metrics(root, cell):
    r = harness.run(cell, 2 ** 31 + 11, 0.5, False, time.perf_counter(),
                    root=root, require_tpu=False, log=lambda m: None)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 6
    assert set(r["metrics"]) == {"tok_s", "hbm_peak_gb", "setup_s"}
    assert r["metrics"]["tok_s"]["value"] > 0
    assert r["compiles_in_window"] == 0
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "check"
    name, limit = tiny.LIMITS[cell]
    assert set(r["check"]) == {name, "failed_requests"}
    assert 0 <= r["check"][name]["value"] <= r["check"][name]["limit"] == limit


def test_traced_run_reads_per_layer_metrics(root):
    r = harness.run("float-decode", 3, 0.2, True, time.perf_counter(),
                    root=root, require_tpu=False, log=lambda m: None)
    assert r["correct"]
    # the CPU has no device plane: the trace's readers find nothing to
    # read and are left out; the scheduler's counter is there
    assert "lane_occupancy" in r["metrics"]
    assert "tok_s" not in r["metrics"]
    assert 0 < r["metrics"]["lane_occupancy"]["value"] <= 100
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_unknown_device_kind_is_an_error(tmp_path):
    root = tiny.make_root(tmp_path, cpu_peaks=False)
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.run("float-decode", 1, 0.1, False, time.perf_counter(),
                    root=root, require_tpu=False)


def test_cli_refuses_to_run_off_the_chip(tmp_path):
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "float-decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "TMPDIR": str(tmp_path)})
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    (tmp_path / "bench").mkdir()
    for f in (ROOT / "bench").glob("*.py"):
        (tmp_path / "bench" / f.name).write_text(f.read_text())
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "float-decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout == ""


def test_sample_holds_the_longest_request():
    reqs = [harness.traffic.Req([1, 2], n, 0) for n in (3, 9, 5, 4)]
    calls = [harness.Call(reqs, [[1, 2] + [7] * r.max_new_tokens
                                 for r in reqs], {}, 1.0)] * 2
    got = harness.sample(calls, 123, 3)
    assert len(got) == 3 and len(got[0][1]) == 9
    assert got == harness.sample(calls, 123, 3)


def test_failed_requests_are_counted():
    reqs = [harness.traffic.Req([1, 2], 3, 0)] * 3
    outs = [[1, 2, 5, 5, 5], [1, 2, 5, 5], [1, 2, 5, 5, 99]]
    call = harness.Call(reqs, outs, {}, 1.0)
    assert harness.count_failed([call], vocab=50) == 2


def test_a_reader_that_reads_nothing_is_left_out_and_said():
    """A trace without the megakernel's events: its roofline is left out
    of the line, and the run names it on stderr."""
    bench = {"end_to_end": [{"name": "tok_s"}],
             "per_layer": [{"name": "megakernel_roofline", "unit": "%",
                            "moves": "tok_s", "workloads": ["cell"]}]}
    ctx = {"trace": {"devices": {"/device:TPU:0": {
        "XLA Ops": [["fusion.1", 0, 10]], "XLA Modules": []}}, "host": []},
        "window": (0, 100)}
    said = []
    assert harness.read_per_layer(bench, "cell", ctx, ROOT,
                                  said.append) == {}
    assert said == ["per_layer megakernel_roofline read nothing in the "
                    "trace: left out"]


def test_sample_spans_every_call_of_the_window():
    calls = [harness.Call([harness.traffic.Req([c, i], 2 + i, 0)
                           for i in range(4)],
                          [[c, i, 7, 7] + [7] * i for i in range(4)], {},
                          1.0) for c in range(3)]
    got = harness.sample(calls, 5, 12)
    assert len(got) == 12 and len(got[0][1]) == 5
    assert sorted(tuple(p) for p, _ in got) == sorted(
        (c, i) for c in range(3) for i in range(4))
