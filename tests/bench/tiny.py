"""A small copy of the benchmark for CPU tests: the same harness, metric
readers and reference, with both configurations cut to 4 layers of width
256 over a vocabulary of 8,192, a 6-request mix on 4 slots, a peaks entry
for the CPU so the harness's lookup by device kind runs, and correctness
limits of their own.

Each cell compares the number its real limits file names, with a limit
set from readings at this size on the CPU (seeds 1–6, 3 sampled
requests): float-decode's mean gap reads at most 2.1e-4 for the program
and at least 6.2e-4 for its control (int8 linears and head);
fused-decode's widest gap reads at most 0.038 for the program and at
least 0.78 for its control (int4 linears).  A token altered where it is
produced and a decode step that leaves the K/V pool unchanged read far
above both limits.
"""
import json
import shutil

from bench.harness import BENCH, ROOT

SIZES = {"hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 4, "vocab_size": 8192}
MIX = {"why": "small", "source": "none", "requests_per_call": 6,
       "prompt_len": {"dist": "uniform", "min": 9, "max": 16},
       "new_tokens": {"dist": "log_uniform", "min": 8, "max": 40},
       "slot_tokens": 64, "shared_prefix": 0}
LIMITS = {"float-decode": ("mean_logit_gap", 4e-4),
          "fused-decode": ("max_logit_gap", 0.2)}
CPU_PEAKS = {"ops_per_s": {"bfloat16": 1e12, "int8": 1e12},
             "hbm_bytes": 1e9, "hbm_bytes_per_s": 1e10}


def make_root(tmp_path, cpu_peaks: bool = True):
    """A checkout-like root: BENCHMARK.json and bench/, cut small."""
    shutil.copytree(BENCH, tmp_path / "bench")
    for name in ("rns-smollm-135m-fused", "smollm-135m"):
        path = tmp_path / "bench" / "configs" / f"{name}.json"
        config = dict(json.loads(path.read_text()), **SIZES)
        config["serving"] = dict(config["serving"], slots=4)
        path.write_text(json.dumps(config))
    (tmp_path / "bench" / "traffic" / "small.json").write_text(
        json.dumps(MIX))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        w["traffic"] = "small"
        number, limit = LIMITS[w["name"]]
        (tmp_path / "bench" / "limits" / f"{w['name']}.json").write_text(
            json.dumps({"sample_requests": 3, number: {"limit": limit}}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    if cpu_peaks:
        path = tmp_path / "bench" / "peaks.json"
        peaks = json.loads(path.read_text())
        peaks["chips"]["cpu"] = CPU_PEAKS
        path.write_text(json.dumps(peaks))
    return tmp_path
