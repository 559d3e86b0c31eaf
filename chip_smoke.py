"""Chip smoke run: the RNS serving path, natively on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded Engine on a 4-chip mesh

One chip runs two phases:

* kernels — every fused-megakernel variant the served configs launch
  (quantize-in with encoded weights, residue-in, residue-in with the modular
  gate, emit="residues"), in the C=5 and C=7 bases of smollm-135m, at the
  decode and the largest prefill row count the serve phase uses.  Each
  launch must compile to a Mosaic ``tpu_custom_call`` and equal the exact
  integer product (emitted residues: the `kernels/ref.py` oracle) bit for
  bit.
* serve — `rns-smollm-135m-resident` and `-fused` at full width (30 layers,
  d_model 576, d_ff 1536, vocab 49152; seeded random weights) through
  `SlotScheduler`: 4 slots, 8 requests, greedy.  Every request returns 32
  in-vocab tokens, logits are finite, and two requests equal
  ``sched.engine.generate([prompt])`` alone (the bit-identity contract).

``--four-chips`` runs only `rns-smollm-135m-fused` on
``Engine(mesh=make_host_mesh(model=4), dist_layout="auto")`` against the
same config on one device and compares greedy tokens.

Timings printed here are smoke timings of one cold and one warm run, not
benchmark metrics.  The script exits non-zero, without its result line,
when JAX's first device is not a TPU or a check fails; its last stdout line
on success is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SEED = 0
SLOTS, BLOCK, SLOT_TOKENS = 4, 16, 512
N_REQUESTS, NEW_TOKENS = 8, 32
PROMPT_MIN, PROMPT_MAX = 16, 200
SERVED = ("rns-smollm-135m-resident", "rns-smollm-135m-fused")


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def _log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ------------------------------------------------------------------ kernels --
def exact_f32(y):
    """The float32 the kernel's MRC epilogue produces for the exact integer
    ``y`` (int64): |y| recombined from 15-bit limbs top-down in float32
    (`multiword.limbs_to_float`), then the sign.  Equals ``y`` for
    |y| < 2^24."""
    import numpy as np

    a = np.abs(y)
    out = np.zeros(a.shape, np.float32)
    for l in reversed(range(5)):
        limb = ((a >> (15 * l)) & 0x7FFF).astype(np.float32)
        out = out * np.float32(1 << 15) + limb
    return np.where(y < 0, -out, out)


def kernel_cases(M: int):
    """The fused launches of the served configs at ``M`` rows.

    Yields ``(name, fn, args, check)``: ``fn(*args)`` runs one launch with
    ``interpret`` left to the kernel, and ``check(out)`` returns None or a
    mismatch message against the exact integer result."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.rns import basis_for_chain, basis_for_int8_matmul
    from repro.core.rns_tensor import RNSTensor
    from repro.kernels import ref, rns_fused_matmul

    d, F = 576, 1536                         # smollm-135m d_model, d_ff
    rng = np.random.default_rng(SEED + M)

    def i8(*shape):
        return rng.integers(-127, 128, shape).astype(np.int64)

    def residues(q, basis):
        return np.stack([np.mod(q, m) for m in basis.moduli]).astype(np.int8)

    def tensor(q, basis, scale=None):
        return RNSTensor(residues=jnp.asarray(residues(q, basis)),
                         scale=scale, basis=basis, bound=127, signed=True)

    def equal(want):
        def check(out):
            out = np.asarray(out)
            if out.shape != want.shape:
                return f"shape {out.shape} != {want.shape}"
            bad = np.flatnonzero(out != want)
            if bad.size:
                i = np.unravel_index(bad[0], want.shape)
                return (f"{bad.size} of {want.size} values differ; first at "
                        f"{tuple(int(v) for v in i)}: {out[i]} != {want[i]}")
            return None
        return check

    b5, b7 = basis_for_int8_matmul(d), basis_for_chain(F)
    ones_col = jnp.ones((1, F), jnp.float32)

    xq, wq = i8(M, d), i8(d, F)
    exact = exact_f32(xq @ wq)
    yield ("C5/int8-in", lambda x, w: rns_fused_matmul(x, w, b5),
           (jnp.asarray(xq, jnp.int8), jnp.asarray(wq, jnp.int8)),
           equal(exact))
    # integer-valued floats with unit scales quantize to xq exactly, and the
    # unit dequant leaves the exact product
    yield ("C5/quantize+encoded",
           lambda x, w, s, c: rns_fused_matmul(x, w, quantize=True,
                                               scale_row=s, scale_col=c),
           (jnp.asarray(xq, jnp.float32), tensor(wq, b5),
            jnp.ones((M, 1), jnp.float32), ones_col),
           equal(exact))
    yield ("C7/residue-in", lambda x, w: rns_fused_matmul(x, w),
           (tensor(xq, b7), tensor(wq, b7)), equal(exact))

    ud, gd, wd = i8(M, F), i8(M, F), i8(F, d)
    yield ("C7/residue-in+gate", lambda x, g, w: rns_fused_matmul(x, w, gate=g),
           (tensor(ud, b7), jnp.asarray(gd, jnp.int8), tensor(wd, b7)),
           equal(exact_f32((ud * gd) @ wd)))

    srow = jnp.asarray(rng.uniform(0.01, 0.1, (M, 1)), jnp.float32)
    scol = jnp.asarray(rng.uniform(0.001, 0.01, (1, F)), jnp.float32)
    x7, w7 = tensor(xq, b7, srow), tensor(wq, b7, scol)
    want_res, want_scale = ref.rns_emit_ref(x7.residues, w7.residues, b7,
                                            srow, scol)
    check_res = equal(np.asarray(want_res))
    check_scale = equal(np.asarray(want_scale))

    def check_emit(out):
        return check_res(out.residues) or check_scale(out.scale)

    yield ("C7/emit-residues",
           lambda x, w, c: rns_fused_matmul(x, w, emit="residues",
                                            scale_col=c),
           (x7, w7, scol), check_emit)


def kernel_phase(rows) -> None:
    import jax

    from repro.core.channel_plan import resolve_interpret

    if resolve_interpret(None):
        raise SmokeFailure("kernels resolve interpret=True on this device")
    for M in rows:
        for name, fn, args, check in kernel_cases(M):
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*args).compile()
            compile_s = time.perf_counter() - t0
            if "tpu_custom_call" not in compiled.as_text():
                raise SmokeFailure(f"{name} M={M}: no tpu_custom_call in the "
                                   "compiled HLO — the launch was not native")
            msg = check(jax.block_until_ready(compiled(*args)))
            if msg:
                raise SmokeFailure(f"{name} M={M}: {msg}")
            _log("kernel", launch=name, M=M, exact=True, native=True,
                 compile_s=round(compile_s, 3))


# -------------------------------------------------------------------- serve --
def _requests(vocab: int):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    return [Request(prompt=rng.integers(1, vocab, int(n)).tolist(),
                    max_new_tokens=NEW_TOKENS, seed=i)
            for i, n in enumerate(lens)]


def _first_divergence(a, b):
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None if len(a) == len(b) else min(len(a), len(b))


def _prefill_logits(engine, prompts):
    batch, _ = engine._pack(prompts)
    logits, cache, pos = engine._prefill(engine.params, batch,
                                         smax=engine.smax)
    return batch, logits, cache, pos


def serve_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.models import transformer as T
    from repro.serve import Engine, SlotScheduler

    params = T.make_params(get_config("smollm-135m"),
                           jax.random.PRNGKey(SEED))
    reqs = _requests(get_config("smollm-135m").vocab_size)
    # the logit probe is request 0 alone: the same (lanes, bucket) prefill
    # executable its solo `generate` compiles, so it adds no compile
    probe = [reqs[0].prompt]

    float_eng = Engine(get_config("smollm-135m"), params, smax=SLOT_TOKENS,
                       lanes=SLOTS)
    _, float_logits, _, _ = _prefill_logits(float_eng, probe)
    float_logits = np.asarray(float_logits, np.float32)
    del float_eng

    failures = []
    for name in SERVED:
        cfg = get_config(name)
        t0 = time.perf_counter()
        sched = SlotScheduler(cfg, params, slots=SLOTS, block_size=BLOCK,
                              slot_tokens=SLOT_TOKENS)
        init_s = time.perf_counter() - t0
        misses = sum(not r["hit"] for r in sched.engine.tune_report)

        t0 = time.perf_counter()
        outs = sched.serve(reqs)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = sched.serve(reqs)
        warm_s = time.perf_counter() - t0
        if again != outs:
            raise SmokeFailure(f"{name}: a second serve of the same requests "
                               "returned different tokens")
        for i, (r, out) in enumerate(zip(reqs, outs)):
            new = out[len(r.prompt):]
            if out[:len(r.prompt)] != r.prompt or len(new) != NEW_TOKENS:
                raise SmokeFailure(f"{name}: request {i} returned "
                                   f"{len(new)} new tokens, not {NEW_TOKENS}")
            if not all(0 <= t < cfg.vocab_size for t in new):
                raise SmokeFailure(f"{name}: request {i} returned an "
                                   "out-of-vocab token")

        identical = 0
        for i, r in enumerate(reqs[:2]):
            solo = sched.engine.generate([r.prompt],
                                         max_new_tokens=NEW_TOKENS)[0]
            pos = _first_divergence(solo, outs[i])
            if pos is None:
                identical += 1
            else:
                # reported after both configs ran, so one run shows all
                failures.append(
                    f"{name}: request {i} through SlotScheduler differs from "
                    f"engine.generate alone at position {pos} (prompt "
                    f"length {len(r.prompt)}): {outs[i][pos:pos + 4]} vs "
                    f"{solo[pos:pos + 4]}")

        batch, logits, cache, pos0 = _prefill_logits(sched.engine, probe)
        step, _ = sched.engine._decode(
            sched.engine.params, cache,
            {"tokens": jnp.argmax(logits, -1)[:, None].astype(jnp.int32)},
            pos0, positions=pos0 - batch["pad"])
        logits = np.asarray(logits, np.float32)[:len(probe)]
        step = np.asarray(step, np.float32)[:len(probe)]
        if not (np.isfinite(logits).all() and np.isfinite(step).all()):
            raise SmokeFailure(f"{name}: non-finite prefill/decode logits")
        ref_l = float_logits[:len(probe)]
        cos = np.sum(logits * ref_l, -1) / (
            np.linalg.norm(logits, axis=-1) * np.linalg.norm(ref_l, axis=-1))
        _log("serve", config=name, requests=len(reqs),
             tokens_served=sum(len(o) - len(r.prompt)
                               for r, o in zip(reqs, outs)),
             bit_identical_solo=f"{identical}/2",
             tune_misses_static_default=misses,
             smoke_init_s=round(init_s, 3), smoke_cold_serve_s=round(cold_s, 3),
             smoke_warm_serve_s=round(warm_s, 3),
             smoke_compile_s_estimate=round(cold_s - warm_s, 3),
             prefill_logits_vs_float_max_abs=float(np.max(np.abs(
                 logits - ref_l))),
             prefill_logits_vs_float_cos_min=float(np.min(cos)))
        del sched
    if failures:
        raise SmokeFailure("; ".join(failures))


# ---------------------------------------------------------------- 4 chips --
def four_chip_phase() -> None:
    import jax

    from repro.configs.base import get_config
    from repro.dist.engine import launch_bases
    from repro.dist.rns_shard import resolve_layout
    from repro.kernels import tune
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.serve import Engine

    if len(jax.devices()) != 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    cfg = get_config("rns-smollm-135m-fused")
    params = T.make_params(cfg, jax.random.PRNGKey(SEED))
    mesh = make_host_mesh(model=4)
    by_c = {len(b.moduli): b for b in launch_bases(cfg)}
    for s in tune.decode_shapes_for(cfg, batch_sizes=(SLOTS,)):
        _log("layout", launch=f"{s['backend']} C{s['C']} "
             f"M{s['M']}xK{s['K']}xN{s['N']}",
             layout=resolve_layout("auto", 4, by_c[s["C"]], s["M"], s["N"],
                                   emit="residues" if s["emit"] else "float"))

    # the serve phase's first prompts at its smax and length: the
    # single-device reference compiles the programs a one-chip run caches
    prompts = [r.prompt for r in _requests(cfg.vocab_size)[:SLOTS]]
    t0 = time.perf_counter()
    sharded = Engine(cfg, params, smax=SLOT_TOKENS, lanes=SLOTS, mesh=mesh,
                     dist_layout="auto").generate(prompts,
                                                  max_new_tokens=NEW_TOKENS)
    sharded_s = time.perf_counter() - t0
    single = Engine(cfg, params, smax=SLOT_TOKENS, lanes=SLOTS).generate(
        prompts, max_new_tokens=NEW_TOKENS)
    for i, (a, b) in enumerate(zip(sharded, single)):
        pos = _first_divergence(a, b)
        if pos is not None:
            raise SmokeFailure(f"sharded vs single-device greedy tokens of "
                               f"prompt {i} differ at token {pos} (prompt "
                               f"length {len(prompts[i])})")
    _log("four_chips", config=cfg.name, prompts=len(prompts),
         new_tokens=NEW_TOKENS, tokens_equal=True,
         smoke_sharded_cold_s=round(sharded_s, 3))


# --------------------------------------------------------------------- main --
def _cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded Engine vs one device")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "serve").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _log("device", **device)
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device is "
              f"{device['platform']})", file=sys.stderr)
        return 1
    entries = _cache_entries(cache_dir)
    _log("compile_cache", dir=cache_dir, entries_at_start=entries)

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chip_phase()
        else:
            kernel_phase(rows=(SLOTS, SLOTS * 256))
            serve_phase()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _log("compile_cache", dir=cache_dir, entries_at_start=entries,
         entries_at_end=_cache_entries(cache_dir),
         smoke_total_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
