"""System analogue: the RNS integer matmul as an accelerator substrate.

Measures (CPU, jit'd jnp — relative numbers transfer to the roofline
analysis, absolute ones are host-CPU):

  * rns_jnp    — the paper's datapath through the fused-XLA backend:
                 residue channels + deferred fold + MRC reconstruction
                 (core/channel_plan dispatch, backend="jnp")
  * rns_pallas — the same datapath through the Pallas kernels
                 (backend="pallas"; interpret mode off-TPU, so off-TPU the
                 number tracks kernel-interpreter overhead, on TPU the
                 actual shipped hot path)
  * int32      — direct int32 matmul (what the RNS path replaces exactly)
  * bf16       — the throughput ceiling XLA gives floating matmuls

plus, per backend, the **conversion split** of the pipeline — forward
conversion / channel matmul / MRC reverse conversion timed as composing
stages (DESIGN.md §10) so the trajectory JSON captures how much of the
integer pipeline the converter endpoints cost (the classic RNS overhead
the ConversionPlan refactor targets),

plus the **fused-vs-staged** comparison (DESIGN.md §13): the Stage ②–⑤
megakernel (`backend="pallas_fused"`, ONE pallas_call) against the staged
three-launch Pallas pipeline, with an estimated-HBM-bytes-moved column from
the inter-stage tensor-traffic model — the staged path writes and re-reads
the (C, M, N) int32 residue tensor (and the (C, K, N) weight residues)
between launches; the fused path's inter-stage values never leave VMEM,

plus the exactness check that is the RNS path's reason to exist: at deep K,
int32 einsum accumulation is exact only below 2^31 and fp32 rounds, while
the RNS path reproduces the int64 oracle.

``--smoke`` runs one tiny shape on ALL backends with hard exactness +
bit-parity asserts — including fused ≡ staged bit-identity AND
fused-not-slower — the CI guard against conversion-path and fused-kernel
regressions that would otherwise only surface in perf runs.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel_plan as cp
from repro.core.conversion_plan import ConversionPlan
from repro.core.rns_linear import _basis_for_k, rns_int_matmul
from repro.core.rns_tensor import RNSTensor

SHAPES = [(64, 512, 64), (128, 2048, 128)]
SMOKE_SHAPES = [(16, 64, 16)]
# Pallas-interpret is python-per-grid-cell off-TPU: bench the small shape
# there, every shape when the kernels compile natively.
ON_TPU = jax.default_backend() == "tpu"


def _time(fn, *args, reps: int = 5):
    """Best-of-reps µs plus the warmup result (so exactness checks don't
    re-execute the kernel — relevant off-TPU where Pallas interprets)."""
    out = jax.block_until_ready(fn(*args))                 # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, out


def _hbm_bytes(M: int, K: int, N: int, C: int, fused: bool) -> int:
    """Estimated HBM bytes moved by the broadcast-datapath pipeline:
    inter-stage tensor traffic (each tensor counted once per producer/
    consumer crossing of the HBM boundary; per-tile operand re-streaming is
    common to both paths and cancels).  Staged: operands in, weight residues
    written + re-read, (C, M, N) int32 residues written + re-read, f32 out.
    Fused: operands in, f32 out — every intermediate stays in VMEM."""
    operands_in = M * K + K * N                     # int8
    out = 4 * M * N                                 # f32
    if fused:
        return operands_in + out
    w_res = C * K * N                               # int8, write + read
    residues = 4 * C * M * N                        # int32, write + read
    return operands_in + 2 * w_res + 2 * residues + out


def _conversion_split(xq, wq, backend: str, reps: int = 3):
    """Time the three pipeline stages of the RNS matmul separately.

    Decomposed on the per-channel (paper-literal) mode, where the stages are
    genuine boundaries that compose: forward converts BOTH operands, matmul
    consumes pre-converted residues, reverse consumes the (C, M, N) epilogue
    residues.  Each stage is its own jit'd callable, so the share is
    reported against the *sum of the stages* — comparing against a fused
    end-to-end timing would mix one dispatch overhead with three and can
    push the "share" past 1.0 at small shapes.
    """
    K = xq.shape[-1]
    basis = _basis_for_k(K)
    conv = ConversionPlan.for_basis(basis)
    moduli = tuple(int(m) for m in basis.moduli)
    plan = cp.ChannelPlan.for_matmul(moduli, K)
    fwd = jax.jit(lambda a, w: (conv.forward(a, backend=backend),
                                conv.forward(w, backend=backend)))
    mm = jax.jit(lambda ar, wr: cp.matmul(ar, wr, moduli, backend=backend,
                                          plan=plan))
    rev = jax.jit(lambda r: conv.reverse(r, backend=backend))
    t_fwd, (a_res, w_res) = _time(fwd, xq, wq, reps=reps)
    t_mm, res = _time(mm, a_res, w_res, reps=reps)
    t_rev, out = _time(rev, res, reps=reps)
    total = t_fwd + t_mm + t_rev
    share = (t_fwd + t_rev) / total if total else float("nan")
    return dict(forward=t_fwd, matmul=t_mm, reverse=t_rev, total=total,
                share=share, out=out)


def _chain_rows(smoke: bool):
    """Residue-resident GLU-MLP chain (DESIGN.md §14) vs the unchained
    per-linear pipeline.

    Chained: ONE `encode_activation` + gate/up residue-in launches + the
    ``emit="residues"`` in-domain requantize + the gated down launch (one MRC
    exit) — `rns_chain_linear` composed exactly as `models/layers.mlp_chain`.
    Unchained: `kernels/ref.rns_fused_chain_ref`, the per-linear staged
    composition under the SAME requantize rule — each linear pays its own
    activation forward conversion (x twice, then the requantized up product
    and the gate branch again before the down matmul) and its own MRC.

    The derived columns carry the conversion-work split: standalone
    activation forward-conversion elements (chained: M·d once; unchained:
    2·M·d + 2·M·F) and reverse-side elements (equal by design — the up
    exit's requantize costs what its MRC did, per output element).  In
    ``--smoke`` the chained jnp path, the chained pallas_fused path
    (interpret off-TPU) and the unchained oracle must agree BIT-identically,
    and chaining must not be slower than the unchained jnp pipeline.
    """
    from repro.core.quant import quantize_int8
    from repro.core.rns import basis_for_chain
    from repro.core.rns_linear import rns_chain_linear
    from repro.core.rns_tensor import encode, encode_activation
    from repro.kernels.ref import rns_fused_chain_ref

    M, d, F = (16, 64, 128) if smoke else (64, 256, 512)
    tag = f"M{M}d{d}F{F}"
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((M, d)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((d, F)) / np.sqrt(d), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((d, F)) / np.sqrt(d), jnp.float32)
    wd = jnp.asarray(rng.standard_normal((F, d)) / np.sqrt(F), jnp.float32)
    basis = basis_for_chain(F)
    C = len(basis.moduli)
    enc_g, enc_u, enc_d = (encode(w, basis) for w in (wg, wu, wd))

    def chained(backend):
        def fn(xf):
            xa = encode_activation(xf, basis, backend=backend)
            gate_f = rns_chain_linear(xa, enc_g, backend=backend)
            up = rns_chain_linear(xa, enc_u, emit="residues", backend=backend)
            gq, sg = quantize_int8(jax.nn.silu(gate_f), axis=-1)
            return rns_chain_linear(up, enc_d, gate=gq, gate_scale=sg,
                                    backend=backend)
        return jax.jit(fn)

    unchained = jax.jit(functools.partial(
        rns_fused_chain_ref, w_gate=enc_g, w_up=enc_u, w_down=enc_d,
        basis=basis))
    t_chain, got_chain = _time(chained("jnp"), x, reps=3)
    t_ref, got_ref = _time(unchained, x, reps=3)
    bitid = np.asarray(got_chain).tobytes() == np.asarray(got_ref).tobytes()
    # conversion-work split (elements; ×(C+1) int ops fwd, ×(C(C+1)/2+3C) rev)
    fwd_chain, fwd_unchain = M * d, 2 * M * d + 2 * M * F
    rev_elems = 2 * M * F + M * d
    if smoke or ON_TPU:
        t_pf, got_pf = _time(chained("pallas_fused"), x, reps=1)
        pf_bitid = np.asarray(got_pf).tobytes() == \
            np.asarray(got_chain).tobytes()
    else:
        t_pf, pf_bitid = float("nan"), None
    if smoke:
        assert bitid, f"chained MLP not bit-identical to unchained at {tag}"
        assert pf_bitid, \
            f"pallas_fused chain diverges from jnp chain at {tag}"
        # same 1.2x scheduler-noise allowance as fused-vs-staged above —
        # chaining drops three of four standalone conversions, so a genuine
        # regression lands far past this
        assert t_chain <= t_ref * 1.2, (
            f"{tag}: chained MLP slower than unchained ({t_chain:.0f}us vs "
            f"{t_ref:.0f}us) — residency regression?")
        print(f"# chain smoke OK: chained==unchained bitwise, "
              f"pallas_fused==jnp, not slower ({t_chain:.0f}us vs "
              f"{t_ref:.0f}us)")
    print(f"# mlp_chain[{tag}] chained={t_chain:.0f}us "
          f"unchained={t_ref:.0f}us bit_identical={bitid} C={C} "
          f"fwd_conv_elems {fwd_chain} vs {fwd_unchain} "
          f"(rev {rev_elems} both)")
    rows = [(f"rns_mlp_chain_{tag}", t_chain,
             f"bit_identical={bitid},vs_unchained={t_chain / t_ref:.2f}x,"
             f"fwd_conv_elems={fwd_chain},"
             f"fwd_conv_elems_unchained={fwd_unchain},"
             f"rev_conv_elems={rev_elems}"),
            (f"rns_mlp_unchained_{tag}", t_ref,
             f"fwd_conv_elems={fwd_unchain},rev_conv_elems={rev_elems}")]
    if pf_bitid is not None:
        rows.append((f"rns_mlp_chain_fused_{tag}", t_pf,
                     f"bit_identical={pf_bitid},interpret={not ON_TPU}"))
    return rows


def run(shapes=None, smoke: bool = False):
    shapes = shapes or (SMOKE_SHAPES if smoke else SHAPES)
    pallas_shapes = shapes if (ON_TPU or smoke) else shapes[:1]
    rows = []
    rng = np.random.default_rng(0)
    for (M, K, N) in shapes:
        xq = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
        wq = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
        xf = xq.astype(jnp.bfloat16)
        wf = wq.astype(jnp.bfloat16)

        rns_jnp = jax.jit(functools.partial(rns_int_matmul, backend="jnp"))
        i32 = jax.jit(lambda a, b: jax.lax.dot_general(
            a.astype(jnp.int32), b.astype(jnp.int32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32))
        bf = jax.jit(lambda a, b: a @ b)

        t_jnp, got = _time(rns_jnp, xq, wq)
        t_i32, _ = _time(i32, xq, wq)
        t_bf, _ = _time(bf, xf, wf)

        # encode-once weights (DESIGN.md §12): the same matmul consuming a
        # pre-encoded RNSTensor — per-call weight-conversion share is what
        # the live path pays and the encoded path doesn't.
        tag = f"M{M}K{K}N{N}"
        # rns_jnp re-specializes on the RNSTensor pytree operand — no
        # separate jit wrapper needed.
        w_enc = RNSTensor.from_int8(wq)
        t_enc, got_enc = _time(rns_jnp, xq, w_enc)
        wconv_share = max(0.0, 1.0 - t_enc / t_jnp)
        enc_exact = np.asarray(got_enc).tobytes() == np.asarray(got).tobytes()
        if smoke:
            assert enc_exact, \
                f"encoded-weights output not bit-identical at {tag}"
        rows.append((f"rns_matmul_encoded_{tag}", t_enc,
                     f"exact={enc_exact},wconv_share={wconv_share:.3f}"))
        print(f"# {tag}: rns_encoded={t_enc:.0f}us vs live={t_jnp:.0f}us "
              f"weight_conv_share={wconv_share:.2f} bit_identical={enc_exact}")

        want = np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
        exact = bool(np.allclose(np.asarray(got), want.astype(np.float64),
                                 rtol=2e-7))
        if smoke:
            assert exact, f"rns_jnp inexact at M{M}K{K}N{N}"

        line = (f"# {tag}: rns_jnp={t_jnp:.0f}us int32={t_i32:.0f}us "
                f"bf16={t_bf:.0f}us exact={exact} "
                f"rns_overhead_vs_int32={t_jnp / t_i32:.1f}x")
        rows.append((f"rns_matmul_jnp_{tag}", t_jnp,
                     f"exact={exact},vs_int32={t_jnp / t_i32:.2f}x"))
        if (M, K, N) in pallas_shapes:
            rns_pal = jax.jit(functools.partial(rns_int_matmul,
                                                backend="pallas"))
            t_pal, got_pal = _time(rns_pal, xq, wq, reps=3)
            pal_exact = bool(np.allclose(np.asarray(got_pal),
                                         want.astype(np.float64), rtol=2e-7))
            if smoke:
                assert pal_exact, f"rns_pallas inexact at {tag}"
                assert np.asarray(got_pal).tobytes() == \
                    np.asarray(got).tobytes(), f"backend parity at {tag}"
            line += f" rns_pallas={t_pal:.0f}us pallas_exact={pal_exact}"
            rows.append((f"rns_matmul_pallas_{tag}", t_pal,
                         f"exact={pal_exact},vs_jnp={t_pal / t_jnp:.2f}x"))

            # fused megakernel vs the staged three-launch pipeline
            # (DESIGN.md §13) — one pallas_call, residues never in HBM.
            rns_fus = jax.jit(functools.partial(rns_int_matmul,
                                                backend="pallas_fused"))
            t_fus, got_fus = _time(rns_fus, xq, wq, reps=3)
            C = len(_basis_for_k(K).moduli)
            hbm_staged = _hbm_bytes(M, K, N, C, fused=False)
            hbm_fused = _hbm_bytes(M, K, N, C, fused=True)
            fus_bitid = np.asarray(got_fus).tobytes() == \
                np.asarray(got_pal).tobytes()
            if smoke:
                assert fus_bitid, \
                    f"fused not bit-identical to staged at {tag}"
                # not-slower guard with a scheduler-noise allowance: at the
                # tiny smoke shape both timings are best-of-reps of a
                # sub-ms call on a shared CI runner, where a descheduled
                # rep can exceed the real ~1.3–2x fused margin — 1.2x
                # still fails any genuine megakernel regression
                assert t_fus <= t_pal * 1.2, (
                    f"{tag}: fused slower than staged ({t_fus:.0f}us vs "
                    f"{t_pal:.0f}us) — megakernel regression?")
            fused_line = (f"#   fused_vs_staged[{tag}] fused={t_fus:.0f}us "
                          f"staged={t_pal:.0f}us "
                          f"speedup={t_pal / t_fus:.2f}x "
                          f"hbm_est_fused={hbm_fused / 1024:.0f}KiB "
                          f"hbm_est_staged={hbm_staged / 1024:.0f}KiB "
                          f"bit_identical={fus_bitid}")
            rows.append((f"rns_matmul_fused_{tag}", t_fus,
                         f"bit_identical={fus_bitid},"
                         f"vs_staged={t_fus / t_pal:.2f}x,"
                         f"hbm_est_bytes={hbm_fused},"
                         f"hbm_est_bytes_staged={hbm_staged}"))
        else:
            fused_line = None
        print(line)
        if fused_line:
            print(fused_line)

        # conversion share of the end-to-end path, per backend
        backends = ["jnp"] + (["pallas"] if (M, K, N) in pallas_shapes
                              else [])
        for be in backends:
            s = _conversion_split(xq, wq, be, reps=1 if smoke else 3)
            if smoke:
                # composed stages must still be the exact int64 product
                assert bool(np.allclose(np.asarray(s["out"]),
                                        want.astype(np.float64),
                                        rtol=2e-7)), f"split {be} {tag}"
            print(f"#   conv_split[{be}] fwd={s['forward']:.0f}us "
                  f"matmul={s['matmul']:.0f}us reverse={s['reverse']:.0f}us "
                  f"total={s['total']:.0f}us conv_share={s['share']:.2f}")
            rows.append((f"rns_conv_split_{be}_{tag}", s["total"],
                         f"fwd={s['forward']:.1f}us,rev={s['reverse']:.1f}us,"
                         f"share={s['share']:.3f}"))
        rows.append((f"int32_matmul_{tag}", t_i32, ""))
        rows.append((f"bf16_matmul_{tag}", t_bf, ""))
    rows.extend(_chain_rows(smoke))
    if smoke:
        print("# smoke OK: jnp/pallas/pallas_fused exact, bit-identical, "
              "fused not slower than staged, chained MLP == unchained")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, both backends, hard exactness asserts"
                         " (the CI conversion-regression guard)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(smoke=args.smoke)
