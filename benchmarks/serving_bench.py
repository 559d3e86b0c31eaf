"""Continuous-batching serving: SlotScheduler vs the static pack-once engine.

A seeded Poisson arrival trace with mixed generation lengths is served two
ways over the SAME model/params:

  * static — `serve.Engine.generate`: requests are grouped FIFO in arrival
    order into batches of ``slots`` and each group runs to the LONGEST
    member's ``max_new_tokens`` (head-of-line blocking: a short request
    burns lane-steps idling behind a long batchmate), with ``slots × smax``
    KV rows reserved throughout;
  * scheduler — `serve.SlotScheduler`: slots free at retirement and the
    next request is admitted mid-flight; K/V lives in the paged pool sized
    BELOW the static reservation, with the common prompt head shared across
    requests (prefix caching).

Reported per trace: sustained useful tok/s (sum of each request's own
``max_new_tokens`` over wall time — tokens a static group generates past a
short request's budget are head-of-line waste, not throughput), p50/p99
request latency — the scheduler's on the wall clock, from when its
virtual clock reaches the request's arrival to the request's last token on
the host (``stats["latency_ms_*"]``), the static engine's in virtual decode steps
(completion − arrival; its clock advances by each group's makespan) — and
peak KV cache bytes
(`launch.costs.{decode_cache_bytes,paged_cache_bytes}` — validated against
the real allocations in tests/test_costs.py).

``--smoke`` asserts the serving contract hard: every scheduler output
BIT-IDENTICAL to ``Engine.generate([prompt])`` run alone at
``smax == slot_tokens``, scheduler tok/s strictly above static, and pool
bytes strictly below the static reservation (CI: benchmarks/run.py §7).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs.base import get_smoke_config
from repro.launch.costs import decode_cache_bytes, paged_cache_bytes
from repro.models import transformer as T
from repro.serve import Engine, Request, SlotScheduler

# (arch, n_requests, long max_new, short max_new)
CONFIGS = [
    ("smollm-135m", 12, 128, 16),
    ("rns-smollm-135m-resident", 8, 160, 16),
    ("mamba2-1.3b", 12, 192, 16),
]
SMOKE_CONFIGS = [("smollm-135m", 12, 128, 16)]

SLOTS = 4
BLOCK = 8
CHUNK = 16          # admission granularity; larger chunk = fewer host syncs
PREFIX = 8          # shared system-prompt head: exactly one block
REPS = 3            # best-of reps: wall timing of ~0.1s host-driven loops
                    # is noisy — take the cleanest pass for BOTH engines


def make_trace(cfg, n: int, long_new: int, short_new: int, seed: int = 0):
    """Seeded Poisson arrivals, mixed lengths: every 4th request is LONG, so
    FIFO groups of `SLOTS` suffer head-of-line blocking by construction.
    All prompts share a PREFIX-token system head (one full block)."""
    rng = np.random.default_rng(seed)
    head = rng.integers(1, cfg.vocab_size, PREFIX).tolist()
    reqs, t = [], 0.0
    for i in range(n):
        t += float(rng.exponential(1.0))
        tail = rng.integers(1, cfg.vocab_size,
                            int(rng.integers(2, 7))).tolist()
        reqs.append(Request(prompt=head + tail,
                            max_new_tokens=long_new if i % SLOTS == 0
                            else short_new,
                            arrival=t))
    return reqs


def _slot_tokens(reqs) -> int:
    need = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    return -(-need // BLOCK) * BLOCK


def serve_static(eng, reqs):
    """FIFO groups of SLOTS in arrival order, each run to the group max;
    outputs truncated to each request's own budget (greedy prefix property).
    Returns (outputs, useful_tokens, latencies_in_steps)."""
    order = sorted(range(len(reqs)), key=lambda i: (reqs[i].arrival, i))
    outs = [None] * len(reqs)
    lat = []
    useful = 0
    clock = 0.0
    for g in range(0, len(order), SLOTS):
        grp = order[g:g + SLOTS]
        tmax = max(reqs[i].max_new_tokens for i in grp)
        batch_out = eng.generate([reqs[i].prompt for i in grp],
                                 max_new_tokens=tmax)
        # the whole group occupies the engine for tmax steps, and cannot
        # start before its last member arrives (pack-once)
        start = max(clock, max(reqs[i].arrival for i in grp))
        clock = start + tmax
        for i, full in zip(grp, batch_out):
            keep = len(reqs[i].prompt) + reqs[i].max_new_tokens
            outs[i] = full[:keep]
            useful += reqs[i].max_new_tokens
            lat.append(clock - reqs[i].arrival)
    return outs, useful, sorted(lat)


def run(configs=None, smoke: bool = False):
    configs = configs or (SMOKE_CONFIGS if smoke else CONFIGS)
    rows = []
    for arch, n, long_new, short_new in configs:
        cfg = get_smoke_config(arch)
        params = T.make_params(cfg, jax.random.PRNGKey(0))
        reqs = make_trace(cfg, n, long_new, short_new)
        slot_tokens = _slot_tokens(reqs)
        # pool sized under the static reservation: covers the trace's worst
        # concurrent residency with slack, yet strictly below SLOTS full
        # lanes — the HBM the paged layout provably returns
        full = SLOTS * (slot_tokens // BLOCK)
        n_blocks = 1 + int(0.9 * full)
        sched = SlotScheduler(cfg, params, slots=SLOTS, block_size=BLOCK,
                              slot_tokens=slot_tokens, n_blocks=n_blocks,
                              decode_chunk=CHUNK)
        eng = sched.engine                      # same weights, same smax

        # ---- correctness first: solo references (also warms compiles)
        solo = [eng.generate([r.prompt], max_new_tokens=r.max_new_tokens)[0]
                for r in reqs]

        # ---- scheduler: warmup pass, then best-of-REPS timed passes
        outs = sched.serve(reqs)
        dt_sched = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            outs = sched.serve(reqs)
            dt_sched = min(dt_sched, time.perf_counter() - t0)
        st = dict(sched.stats)
        tps_sched = st["new_tokens"] / dt_sched

        # ---- static: warmup pass, then best-of-REPS timed passes
        serve_static(eng, reqs)
        dt_static = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            outs_static, useful, lat_static = serve_static(eng, reqs)
            dt_static = min(dt_static, time.perf_counter() - t0)
        tps_static = useful / dt_static

        sched_bytes = paged_cache_bytes(cfg, n_blocks, BLOCK, SLOTS)
        static_bytes = decode_cache_bytes(cfg, SLOTS, slot_tokens)
        identical = outs == solo
        static_ok = outs_static == solo
        p50s = st["latency_ms_p50"]
        p99s = st["latency_ms_p99"]
        p50t = lat_static[len(lat_static) // 2]
        p99t = lat_static[min(len(lat_static) - 1,
                              int(np.ceil(0.99 * len(lat_static))) - 1)]
        tag = f"{arch}_n{n}_L{long_new}S{short_new}"
        print(f"# {tag}: sched={tps_sched:.1f} tok/s static={tps_static:.1f} "
              f"tok/s ({tps_sched / tps_static:.2f}x)  latency p50/p99 "
              f"sched={p50s:.1f}/{p99s:.1f} ms static={p50t:.0f}/{p99t:.0f} "
              f"steps  cache {sched_bytes}B vs {static_bytes}B "
              f"({sched_bytes / static_bytes:.2f}x)  prefix_hits="
              f"{st['prefix_hits']} bit_identical={identical}")
        rows.append((f"serving_sched_{tag}", tps_sched,
                     f"p50={p50s:.1f},p99={p99s:.1f},ms,"
                     f"cache_bytes={sched_bytes},"
                     f"prefix_hits={st['prefix_hits']},"
                     f"identical={identical}"))
        rows.append((f"serving_static_{tag}", tps_static,
                     f"p50={p50t:.0f},p99={p99t:.0f},steps,"
                     f"cache_bytes={static_bytes}"))
        if smoke:
            assert identical, (
                f"{tag}: scheduler output diverged from solo Engine.generate")
            assert static_ok, (
                f"{tag}: static grouped output diverged from solo")
            assert tps_sched > tps_static, (
                f"{tag}: scheduler not faster ({tps_sched:.1f} vs "
                f"{tps_static:.1f} tok/s) — continuous batching should beat "
                "head-of-line blocking on this trace")
            assert sched_bytes < static_bytes, (
                f"{tag}: paged pool ({sched_bytes}B) not below the static "
                f"reservation ({static_bytes}B)")
    if smoke:
        print("# smoke OK: scheduler bit-identical to solo, faster than "
              "static, smaller KV footprint")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small trace + hard asserts (CI)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, val, note in run(smoke=args.smoke):
        print(f"{name}: {val:.1f} tok/s  {note}")
