"""Benchmark driver: one section per paper table/figure.

Prints a final `name,us_per_call,derived` CSV (harness contract) and writes
the same rows as machine-readable **BENCH_9.json** — the perf-trajectory
artifact (commit hash + device + per-row values: the matmul
forward/matmul/reverse conversion split, the fused-vs-staged megakernel row
with its estimated-HBM-bytes columns, and decode tok/s), uploaded by CI so
the trajectory is diffable across runs instead of living in scrollback.

Usage: PYTHONPATH=src python -m benchmarks.run [--smoke] [--json PATH]
``--smoke`` runs every section that supports it in its small hard-assert
mode (the CI configuration) — sections without a smoke mode run as usual.
"""
from __future__ import annotations

import inspect
import json
import subprocess
import sys
import time
import traceback

BENCH_JSON = "BENCH_9.json"


def _commit() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "HEAD"], text=True,
            stderr=subprocess.DEVNULL).strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def _run_section(mod, smoke: bool):
    """Invoke a section's run(), passing smoke= only where supported."""
    if smoke and "smoke" in inspect.signature(mod.run).parameters:
        return mod.run(smoke=True)
    return mod.run()


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + hard asserts where a section "
                         "supports them (the CI configuration)")
    ap.add_argument("--json", default=BENCH_JSON,
                    help=f"machine-readable output path ({BENCH_JSON})")
    args = ap.parse_args(argv)

    import jax

    from . import (analytical_model, app_level, circuit_level, decode_bench,
                   matmul_bench, serving_bench, synthesis_tables)
    sections = [
        ("Table I / Fig. 4 (analytical model)", analytical_model),
        ("Fig. 5 analogue (per-modulus circuit level)", circuit_level),
        ("Tables II-III (synthesis echo + ratios)", synthesis_tables),
        ("Fig. 8 (application-level surface)", app_level),
        ("RNS matmul system analogue", matmul_bench),
        ("Decode throughput (host vs scan, live vs encoded)", decode_bench),
        ("Continuous-batching serving (scheduler vs static)", serving_bench),
    ]
    all_rows = []
    failures = []
    for title, mod in sections:
        print(f"\n===== {title} =====")
        try:
            all_rows.extend(_run_section(mod, args.smoke))
        except Exception:
            failures.append(title)
            traceback.print_exc()
    print("\n===== summary CSV =====")
    print("name,us_per_call,derived")
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")

    # machine-readable trajectory artifact — written even on section
    # failure so a partial run still leaves evidence.
    payload = {
        "bench": 9,
        "commit": _commit(),
        "device": jax.default_backend(),
        "smoke": bool(args.smoke),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "failures": failures,
        "rows": [
            {"name": name, "value": round(float(us), 3),
             "derived": dict(
                 kv.split("=", 1) for kv in derived.split(",") if "=" in kv)}
            for name, us, derived in all_rows
        ],
    }
    with open(args.json, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"# wrote {args.json} ({len(all_rows)} rows, commit "
          f"{payload['commit'][:12]})")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
