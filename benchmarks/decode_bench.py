"""Decode throughput: host-loop engine vs on-device scan engine.

Measures tokens/s for `serve.engine.Engine.generate` under its two decode
orchestrations (identical math — shared prefill/decode_step — identical
greedy tokens):

  * host  — per-token Python loop: one jitted decode_step dispatch plus
            `int()` host syncs per token per sequence (the pre-scan engine);
  * scan  — ONE jitted `lax.scan` over the new-token axis: sampling, the
            EOS/done mask, and cache updates stay on device; tokens land on
            the host once at the end.

The gap is pure deferred-synchronization win (DESIGN.md §11) — the serving
analogue of the paper's deferred carry propagation: per-token host syncs are
the carry chains of the decode loop, and the scan engine defers them all to
one materialization.

Timing excludes compilation (a warmup generate of the same shape runs
first).  ``--smoke`` runs one small config with hard asserts — greedy
host/scan token equality AND scan strictly faster — the CI guard against
decode-path regressions (a reintroduced per-token sync shows up as a
throughput cliff long before anyone reads a profile).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs.base import get_smoke_config
from repro.models import transformer as T
from repro.serve.engine import Engine

# (arch, batch, prompt lengths are ragged on purpose, new tokens)
CONFIGS = [
    ("smollm-135m", 4, (3, 7, 11, 16), 64),
    ("h2o-danube-1.8b", 4, (3, 7, 11, 16), 64),      # SWA ring caches
    ("mamba2-1.3b", 4, (3, 7, 11, 16), 64),          # SSM state caches
]
SMOKE_CONFIGS = [("smollm-135m", 2, (3, 9), 32)]

# rns_int8 decode: live per-step weight quantization+conversion vs weights
# encoded to residue-domain RNSTensors once at Engine.__init__ (the
# encode_weights LinearSpec flag, DESIGN.md §12).  Greedy outputs must be
# bit-identical; the gap is the per-call Stage-②-for-weights cost.
ENCODED_CONFIGS = [("rns-smollm-135m", 2, (3, 9), 32)]
SMOKE_ENCODED_CONFIGS = [("rns-smollm-135m", 2, (3, 9), 16)]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


def _conv_flops_per_token(cfg, B: int) -> float:
    """Analytic activation-conversion work per decoded token (int ops), from
    the loop-correct cost model (`launch/costs.py`): quantize + C-mod forward
    conversion per linear input element plus the MRC fold ladder per output
    element.  0.0 for bf16 configs (no rns datapath); residue-resident
    configs (DESIGN.md §14) drop the duplicated forward conversions, which
    is exactly what this column makes visible in the trajectory JSON."""
    from repro.configs.base import ShapeConfig
    from repro.launch.costs import analytic_cost

    c = analytic_cost(cfg, ShapeConfig("bench", 128, B, "decode"),
                      n_pods=1, data=1, model=1)
    return (c.breakdown.get("flops_act_fwd_conv", 0.0)
            + c.breakdown.get("flops_act_rev_conv", 0.0)) / B


def _time_generate(eng, prompts, T_new, engine, reps=3):
    out = eng.generate(prompts, max_new_tokens=T_new, engine=engine)  # warmup
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        eng.generate(prompts, max_new_tokens=T_new, engine=engine)
        best = min(best, time.perf_counter() - t0)
    n_tokens = sum(len(o) - len(p) for o, p in zip(out, prompts))
    return n_tokens / best, out


def run(configs=None, smoke: bool = False):
    default_set = configs is None
    configs = configs or (SMOKE_CONFIGS if smoke else CONFIGS)
    rows = []
    for arch, B, lens, T_new in configs:
        cfg = get_smoke_config(arch)
        params = T.make_params(cfg, jax.random.PRNGKey(0))
        smax = max(lens) + T_new + 16
        eng = Engine(cfg, params, smax=smax)
        prompts = _prompts(cfg, lens)

        tps_host, out_host = _time_generate(eng, prompts, T_new, "host")
        tps_scan, out_scan = _time_generate(eng, prompts, T_new, "scan")
        equal = out_host == out_scan
        speedup = tps_scan / tps_host
        tag = f"{arch}_B{B}_T{T_new}"
        conv_tok = _conv_flops_per_token(cfg, B)
        print(f"# {tag}: host={tps_host:.1f} tok/s scan={tps_scan:.1f} tok/s "
              f"speedup={speedup:.2f}x greedy_equal={equal} "
              f"conv_flops_per_tok={conv_tok:.0f}")
        rows.append((f"decode_host_{tag}", tps_host, ""))
        rows.append((f"decode_scan_{tag}", tps_scan,
                     f"speedup={speedup:.2f}x,equal={equal},"
                     f"conv_flops_per_tok={conv_tok:.0f}"))
        if smoke:
            assert equal, f"{tag}: host and scan engines diverged"
            assert tps_scan > tps_host, (
                f"{tag}: scan engine not faster ({tps_scan:.1f} vs "
                f"{tps_host:.1f} tok/s) — per-token sync regression?")
    if smoke:
        print("# smoke OK: scan engine faster, host/scan greedy-identical")
    if default_set:
        # default benchmark set ⇒ include the encoded-weights rns section;
        # explicit caller-chosen configs stay exactly what was asked for.
        rows += run_encoded(smoke=smoke)
    return rows


def run_encoded(configs=None, smoke: bool = False):
    """Live-quantization vs encode-once rns decode (scan engine both)."""
    import dataclasses

    configs = configs or (SMOKE_ENCODED_CONFIGS if smoke else ENCODED_CONFIGS)
    rows = []
    for arch, B, lens, T_new in configs:
        cfg_live = get_smoke_config(arch)
        cfg_enc = dataclasses.replace(cfg_live, encode_weights=True)
        params = T.make_params(cfg_live, jax.random.PRNGKey(0))
        smax = max(lens) + T_new + 16
        eng_live = Engine(cfg_live, params, smax=smax)
        eng_enc = Engine(cfg_enc, params, smax=smax)
        prompts = _prompts(cfg_live, lens)

        tps_live, out_live = _time_generate(eng_live, prompts, T_new, "scan")
        tps_enc, out_enc = _time_generate(eng_enc, prompts, T_new, "scan")
        equal = out_live == out_enc
        speedup = tps_enc / tps_live
        tag = f"{arch}_B{B}_T{T_new}"
        conv_tok = _conv_flops_per_token(cfg_enc, B)
        # same cfg but domain="residue": the chained datapath's per-token
        # activation-conversion budget — the analytic size of the win the
        # resident configs bank (the timings above are live-vs-encoded; the
        # resident kernel path is covered by matmul_bench's chain row).
        conv_res = _conv_flops_per_token(
            dataclasses.replace(cfg_enc, linear_domain="residue"), B)
        print(f"# {tag}: live={tps_live:.1f} tok/s encoded={tps_enc:.1f} "
              f"tok/s speedup={speedup:.2f}x greedy_equal={equal} "
              f"(per-step weight quant+conversion share of decode) "
              f"conv_flops_per_tok={conv_tok:.0f} resident={conv_res:.0f}")
        rows.append((f"decode_rns_live_{tag}", tps_live, ""))
        rows.append((f"decode_rns_encoded_{tag}", tps_enc,
                     f"speedup={speedup:.2f}x,equal={equal},"
                     f"conv_flops_per_tok={conv_tok:.0f},"
                     f"conv_flops_per_tok_resident={conv_res:.0f}"))
        if smoke:
            assert equal, (
                f"{tag}: encoded-weights greedy output diverged from the "
                "live-quantization path")
            # "not slower": best-of-reps with a 2% timing-noise floor — the
            # encoded path strictly removes per-step work.
            assert tps_enc >= 0.98 * tps_live, (
                f"{tag}: encoded decode slower ({tps_enc:.1f} vs "
                f"{tps_live:.1f} tok/s) — encode-once regression?")
    if smoke:
        print("# smoke OK: encoded-weights decode bit-identical & not slower")
    rows += run_comms(smoke=smoke)
    return rows


def run_comms(ndev: int = 8, smoke: bool = False):
    """Analytic bytes-on-wire per decode step for the sharded launch layouts.

    The `launch.costs.comms_bytes_decode` column (DESIGN.md §17): per-device
    ring-collective wire bytes of ONE sharded decode step over an
    ``ndev``-way model axis, under each forced layout and the per-launch
    "auto" choice.  Not a timing — the host-mesh parity platform has no real
    interconnect — but the model the Engine's layout preference is chosen
    by, pinned into the trajectory JSON so a regression in the cost model
    (or a layout flip) is visible in review."""
    from repro.launch.costs import comms_bytes_decode

    rows = []
    B = 2
    for arch in ("rns-smollm-135m-fused", "rns-smollm-135m-resident"):
        cfg = get_smoke_config(arch)
        by = {lay: comms_bytes_decode(cfg, B, ndev=ndev, layout=lay)
              for lay in ("channel", "column", "auto")}
        tag = f"{arch}_B{B}_n{ndev}"
        print(f"# {tag}: comms_bytes/step channel={by['channel']:.0f} "
              f"column={by['column']:.0f} auto={by['auto']:.0f}")
        rows.append((f"decode_comms_{tag}", by["auto"],
                     f"channel={by['channel']:.0f},column={by['column']:.0f},"
                     f"ndev={ndev}"))
        if smoke:
            assert by["auto"] <= min(by["channel"], by["column"]) + 1e-6, (
                f"{tag}: auto layout costs more wire than a forced layout")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="one small config, hard equality + speedup asserts"
                         " (the CI decode-path regression guard)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(smoke=args.smoke)
