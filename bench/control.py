"""Readings that set a cell's correctness limit, on the chip, in one
process: for each seed, the program's widest logit gap and its control's.

    python3 bench/control.py --workload fused-decode --seeds 1-12
    python3 bench/control.py --workload fused-decode --seeds 1-3 \
        --fault state_unchanged

One set-up serves every seed: after the first, the weights of each seed
are made and prepared as `serve.Engine` prepares them (encoded once where
the configuration encodes them) and put in the scheduler's engine, whose
compiled programs take the weights as an argument.  Each seed then runs
the window's first call of that seed — the cell's own load — and compares
the same sample a benchmark run compares, against the configuration's own
reference.  The control is that reference computed at the configuration's
lower precision (its ``control`` entry), read on the same prompts and
served tokens.  The benchmark's own runs never run this.  Prints one JSON
line per seed, then the lower reading (the largest of the program's) and
the upper one (the smallest of the control's).  With ``--fault`` the
program runs with that fault of `bench.faults` planted, in place of the
control, and the last line gives the smallest reading of each number
beside the cell's limit.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def seeds_of(text: str):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi) + 1)) if hi else \
        [int(s) for s in text.split(",")]


def swap_weights(sched, seed: int) -> None:
    import jax

    from bench import traffic
    from repro.core.rns_tensor import encode_params
    from repro.models import transformer as T

    cfg = sched.cfg
    key = jax.random.PRNGKey(traffic.weight_seed(seed))
    params = jax.jit(lambda k: T.make_params(cfg, k))(key)
    spec = cfg.linear_spec
    if spec.is_rns and spec.encode_weights:
        params = encode_params(params, backend=spec.backend)
    sched.engine.params = jax.block_until_ready(params)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="'1-12' or '3,5,9'")
    ap.add_argument("--fault", default=None,
                    help="plant this fault of bench/faults.py")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench import faults, harness

    spec = harness.resolve_cell(harness.load_benchmark(), args.workload)
    config, mix, limits = spec["config"], spec["mix"], spec["limits"]
    ref = spec["reference"]
    device = harness.device_info(spec["cell"])
    if args.fault:
        faults.FAULTS[args.fault](setattr)
    seeds = seeds_of(args.seeds)
    sched = harness.build(config, mix, seeds[0])
    harness.warm(sched, mix, seeds[0])
    lower = harness.control_lower(config)
    prog, ctrl = [], []
    for i, seed in enumerate(seeds):
        if i:
            swap_weights(sched, seed)
        t = time.perf_counter()
        reqs = harness.traffic.call(mix, seed, 0, sched.cfg.vocab_size)
        outs = sched.serve(harness.program_requests(reqs))
        call = harness.Call(reqs, outs, dict(sched.stats),
                            time.perf_counter() - t)
        seqs = harness.sample([call], seed, limits["sample_requests"])
        p = harness.gap_readings(ref, config, seed, seqs,
                                 mix["slot_tokens"])
        prog.append(harness.numbers(p))
        line = {"seed": seed, "program": prog[-1], "program_per_request": p}
        if not args.fault:
            c = harness.gap_readings(ref, config, seed, seqs,
                                     mix["slot_tokens"], lower)
            ctrl.append(harness.numbers(c))
            line.update(control=ctrl[-1], control_per_request=c)
        line.update(failed=harness.count_failed([call],
                                                config["vocab_size"]),
                    call_s=call.seconds, chunks=call.stats["chunks"])
        print(json.dumps(line), flush=True)
    summary = {}
    for name in prog[0]:
        if args.fault:
            summary[name] = {"fault_lowest": min(r[name] for r in prog),
                             "limit": limits.get(name, {}).get("limit")}
            continue
        lo = max(r[name] for r in prog)
        up = min(r[name] for r in ctrl)
        summary[name] = {"lower_reading": lo, "upper_reading": up,
                         "ratio": up / max(lo, 1e-30)}
    print(json.dumps({"workload": args.workload, "device": device,
                      "fault": args.fault,
                      "control": {"bits": lower[0], "matmuls": lower[1]},
                      "sample_requests": limits["sample_requests"],
                      "seeds": seeds, **summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
