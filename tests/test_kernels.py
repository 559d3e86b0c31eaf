"""Pallas kernel sweeps: shapes/dtypes vs the pure-jnp oracles (interpret
mode executes the kernel bodies on CPU — bit-exact for the integer kernels).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rns import basis_for_accumulation
from repro.kernels import (flash_attention, fold, rns_fused_matmul,
                           rns_matmul, rns_modmul)
from repro.kernels import ref

MODULI = basis_for_accumulation(1024 * 127 * 127).moduli


@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (32, 64, 32, 32, 32, 32),
    (48, 96, 80, 32, 32, 32),      # padding on every dim
    (128, 256, 128, 64, 64, 128),
    (8, 1024, 8, 8, 8, 256),       # deep K accumulation
])
def test_rns_matmul_sweep(M, K, N, bm, bn, bk):
    rng = np.random.default_rng(M * K + N)
    xq = rng.integers(-127, 128, (M, K)).astype(np.int64)
    wq = rng.integers(-127, 128, (K, N)).astype(np.int64)
    a = np.stack([np.mod(xq, m) for m in MODULI]).astype(np.int8)
    b = np.stack([np.mod(wq, m) for m in MODULI]).astype(np.int8)
    got = np.asarray(rns_matmul(jnp.asarray(a), jnp.asarray(b), MODULI,
                                block_m=bm, block_n=bn, block_k=bk))
    want = np.stack([np.mod(xq @ wq, m) for m in MODULI])
    assert np.array_equal(got, want)


def test_rns_matmul_matches_ref():
    rng = np.random.default_rng(7)
    a = np.stack([rng.integers(0, m, (16, 32)) for m in MODULI]).astype(np.int8)
    b = np.stack([rng.integers(0, m, (32, 24)) for m in MODULI]).astype(np.int8)
    got = np.asarray(rns_matmul(jnp.asarray(a), jnp.asarray(b), MODULI,
                                block_m=16, block_n=8, block_k=16))
    want = np.asarray(ref.rns_matmul_ref(jnp.asarray(a), jnp.asarray(b),
                                         MODULI))
    assert np.array_equal(got, want)


def test_rns_matmul_overflow_guard():
    a = jnp.zeros((len(MODULI), 8, 2**21), jnp.int8)
    b = jnp.zeros((len(MODULI), 2**21, 8), jnp.int8)
    with pytest.raises(ValueError):
        rns_matmul(a, b, MODULI)


@pytest.mark.parametrize("S,blk", [(64, 64), (1000, 128), (4096, 1024)])
def test_rns_modmul_sweep(S, blk):
    rng = np.random.default_rng(S)
    a = np.stack([rng.integers(0, m, S) for m in MODULI]).astype(np.int32)
    b = np.stack([rng.integers(0, m, S) for m in MODULI]).astype(np.int32)
    got = np.asarray(rns_modmul(jnp.asarray(a), jnp.asarray(b), MODULI,
                                block=blk))
    want = np.stack([(a[c].astype(np.int64) * b[c]) % MODULI[c]
                     for c in range(len(MODULI))])
    assert np.array_equal(got, want)


@pytest.mark.parametrize("bound", [2**15, 2**25, 2**31 - 1])
def test_fold_sweep(bound):
    rng = np.random.default_rng(bound % 1000)
    x = np.stack([rng.integers(0, bound, 600) for _ in MODULI]).astype(np.int64)
    got = np.asarray(fold(jnp.asarray(x.astype(np.int32)), MODULI, bound,
                          block=256))
    want = np.stack([x[c] % MODULI[c] for c in range(len(MODULI))])
    assert np.array_equal(got, want)


def test_fold_includes_pow2_channel():
    mods = (1024, 47, 31)
    x = np.array([[2**30, 1023, 1024], [5000, 46, 47], [12345, 1, 0]],
                 dtype=np.int32)
    got = np.asarray(fold(jnp.asarray(x), mods, 2**31 - 1, block=4))
    want = np.stack([x[c].astype(np.int64) % mods[c] for c in range(3)])
    assert np.array_equal(got, want)


# ----------------------------------------------------- fused megakernel ----
# The Stage ②–⑤ single-launch pipeline (kernels/rns_fused.py, DESIGN.md §13)
# must be bit-identical to BOTH staged backends on every datapath and basis.

def _bases():
    from repro.core.rns import N8_CHANNELS, RNSBasis, paper_n5_basis

    return [
        ("paper-n5", paper_n5_basis()),                  # incl. the 2^10
        ("n8", RNSBasis(name="n8-set", moduli=N8_CHANNELS)),
        # Table III's full n=11 *channel set* is not pairwise coprime
        # (gcd(2045, 1025) = 5) so it cannot be an MRC basis — the fused
        # pipeline (which must reverse-convert) runs on its maximal
        # coprime subset of 2^11±δ channels.
        ("n11", RNSBasis(name="n11-sub", moduli=(2051, 2039, 2057, 3071))),
    ]


@pytest.mark.parametrize("name,basis", _bases(), ids=lambda b: getattr(
    b, "name", b))
@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (9, 48, 7, 8, 8, 16),          # padding on every dim
    (32, 64, 32, 32, 32, 32),
])
def test_fused_matches_staged_all_bases(name, basis, M, K, N, bm, bn, bk):
    rng = np.random.default_rng(M * K + N)
    xq = jnp.asarray(rng.integers(-128, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.integers(-128, 128, (K, N)), jnp.int8)
    got = np.asarray(rns_fused_matmul(xq, wq, basis, block_m=bm, block_n=bn,
                                      block_k=bk))
    want = np.asarray(ref.rns_fused_matmul_ref(xq, wq, basis))
    assert got.tobytes() == want.tobytes()
    oracle = np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    assert np.array_equal(got.astype(np.int64), oracle)


@pytest.mark.parametrize("datapath", ["live", "encoded"])
def test_fused_both_datapaths_three_way_parity(datapath):
    """jnp ↔ pallas ↔ pallas_fused bit-parity through rns_int_matmul on the
    live-int8 and pre-encoded RNSTensor weight datapaths."""
    from repro.core.rns_linear import rns_int_matmul
    from repro.core.rns_tensor import RNSTensor

    rng = np.random.default_rng(3)
    xq = jnp.asarray(rng.integers(-128, 128, (11, 96)), jnp.int8)
    wq = jnp.asarray(rng.integers(-128, 128, (96, 13)), jnp.int8)
    w = RNSTensor.from_int8(wq) if datapath == "encoded" else wq
    outs = {be: np.asarray(rns_int_matmul(xq, w, backend=be))
            for be in ("jnp", "pallas", "pallas_fused")}
    assert outs["jnp"].tobytes() == outs["pallas"].tobytes()
    assert outs["jnp"].tobytes() == outs["pallas_fused"].tobytes()
    oracle = np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    assert np.array_equal(outs["pallas_fused"].astype(np.int64), oracle)


def test_fused_int8_corners_including_minus_128():
    """Full int8 range incl. the −128 saturated operand: the signed bound is
    K·128·(m−1) and the worst-case accumulator K·128·128 must fold and
    reverse-convert exactly through the one-launch pipeline."""
    from repro.core.rns_linear import rns_int_matmul

    M, K, N = 4, 96, 8
    rng = np.random.default_rng(42)
    xq = rng.integers(-128, 128, (M, K)).astype(np.int8)
    wq = rng.integers(-128, 128, (K, N)).astype(np.int8)
    xq[0, :] = -128
    wq[:, 0] = -128
    xq[1, :] = 127
    wq[:, 1] = 127
    got = np.asarray(rns_int_matmul(jnp.asarray(xq), jnp.asarray(wq),
                                    backend="pallas_fused"))
    want = xq.astype(np.int64) @ wq.astype(np.int64)
    assert int(want[0, 0]) == K * 128 * 128      # the worst-case accumulator
    assert np.array_equal(got.astype(np.int64), want)


def test_fused_dense_seed_golden_regression():
    """The seed-golden rns_dense bytes (pinned since PR 1) through the fused
    backend — the megakernel may not move a single output bit."""
    from test_channel_plan import _GOLDEN_DENSE_HEX, _GOLDEN_INT

    from repro.core.rns_linear import rns_dense, rns_int_matmul

    rng = np.random.default_rng(1234)
    x = rng.standard_normal((6, 96)).astype(np.float32)
    w = rng.standard_normal((96, 10)).astype(np.float32)
    y = np.asarray(rns_dense(jnp.asarray(x), jnp.asarray(w), "pallas_fused"))
    assert y.astype(np.float32).tobytes().hex() == _GOLDEN_DENSE_HEX
    xq = rng.integers(-127, 128, (5, 64)).astype(np.int8)
    wq = rng.integers(-127, 128, (64, 7)).astype(np.int8)
    yi = np.asarray(rns_int_matmul(jnp.asarray(xq), jnp.asarray(wq),
                                   backend="pallas_fused"))
    assert yi.astype(np.int64).tolist() == _GOLDEN_INT


def test_fused_single_pallas_call_jaxpr(analysis):
    """The acceptance contract: the WHOLE quantize → forward → matmul →
    fold → reverse → dequant rns_dense pipeline lowers to exactly ONE
    pallas_call (the staged backend lowers to three)."""
    from repro.core.rns_linear import rns_dense

    x = jnp.ones((6, 96), jnp.float32)
    w = jnp.ones((96, 10), jnp.float32)
    analysis.assert_clean(lambda a, b: rns_dense(a, b, "pallas_fused"), None,
                          x, w, expect_pallas_calls=1, subject="fused")
    analysis.assert_clean(lambda a, b: rns_dense(a, b, "pallas"), None,
                          x, w, expect_pallas_calls=3, subject="staged")


def test_fused_scale_epilogue_parity():
    """The generic fused-dequant scale replays reverse(scale=...)'s single
    broadcast multiply bit-for-bit."""
    from repro.core.rns_linear import rns_int_matmul

    rng = np.random.default_rng(5)
    xq = jnp.asarray(rng.integers(-128, 128, (7, 64)), jnp.int8)
    wq = jnp.asarray(rng.integers(-128, 128, (64, 9)), jnp.int8)
    s = jnp.asarray(rng.standard_normal((7, 9)), jnp.float32)
    want = np.asarray(rns_int_matmul(xq, wq, backend="jnp", scale=s))
    got = np.asarray(rns_int_matmul(xq, wq, backend="pallas_fused", scale=s))
    assert got.tobytes() == want.tobytes()


def test_fused_rejects_bad_operands():
    basis = basis_for_accumulation(96 * 128 * 128)
    xq = jnp.zeros((4, 96), jnp.int8)
    with pytest.raises(ValueError, match="explicit basis"):
        rns_fused_matmul(xq, jnp.zeros((5, 96, 8), jnp.int8))
    with pytest.raises(ValueError, match="channels"):
        rns_fused_matmul(xq, jnp.zeros((2, 96, 8), jnp.int8), basis)
    with pytest.raises(ValueError, match="scale_row"):
        rns_fused_matmul(jnp.zeros((4, 96), jnp.float32),
                         jnp.zeros((96, 8), jnp.int8), basis, quantize=True)


ATTN_CASES = [
    # (B, H, Sq, Sk, D, window, softcap, dtype)
    (2, 3, 64, 64, 32, None, None, jnp.float32),
    (1, 2, 128, 128, 32, 32, None, jnp.float32),
    (1, 2, 64, 64, 32, None, 30.0, jnp.float32),
    (1, 2, 1, 96, 32, None, None, jnp.float32),      # decode shape
    (1, 1, 100, 100, 16, 24, 50.0, jnp.float32),     # padding + both extras
    (2, 2, 64, 64, 64, None, None, jnp.bfloat16),    # dtype sweep
]


@pytest.mark.parametrize("B,H,Sq,Sk,D,win,cap,dtype", ATTN_CASES)
def test_flash_attention_sweep(B, H, Sq, Sk, D, win, cap, dtype):
    rng = np.random.default_rng(B * Sq + Sk)
    q = jnp.asarray(rng.standard_normal((B, H, Sq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, H, Sk, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, H, Sk, D)), dtype)
    got = flash_attention(q, k, v, causal=True, window=win, softcap=cap,
                          block_q=32, block_k=32)
    want = ref.attention_ref(q, k, v, causal=True, window=win, softcap=cap)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("B,H,Sq,Sk,D,win,cap", [
    (3, 2, 64, 64, 32, None, None),
    (2, 2, 64, 64, 32, 16, 30.0),
    (2, 1, 1, 96, 32, None, None),                   # decode shape
])
def test_flash_attention_pad_mask(B, H, Sq, Sk, D, win, cap):
    """Ragged-batch validity: the kernel's pad path == the padded oracle,
    and each sequence's valid rows == its unpadded solo run (no pad leak)."""
    rng = np.random.default_rng(7 * B + Sk)
    q = jnp.asarray(rng.standard_normal((B, H, Sq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, Sk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, Sk, D)), jnp.float32)
    pad = jnp.asarray(rng.integers(0, Sk - 1, B), jnp.int32)
    got = flash_attention(q, k, v, causal=True, window=win, softcap=cap,
                          pad=pad, block_q=32, block_k=32)
    want = ref.attention_ref(q, k, v, causal=True, window=win, softcap=cap,
                             pad=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if Sq == Sk:
        for b in range(B):
            p = int(pad[b])
            solo = flash_attention(q[b:b + 1, :, p:], k[b:b + 1, :, p:],
                                   v[b:b + 1, :, p:], causal=True, window=win,
                                   softcap=cap, block_q=32, block_k=32)
            np.testing.assert_allclose(np.asarray(got[b, :, p:]),
                                       np.asarray(solo[0]), atol=2e-5)


def test_channel_schedules_shared():
    """Kernel and oracle provably share the same fold ladders."""
    sched, mods, n_sub = ref.channel_schedules(MODULI, 1024 * 46 * 46)
    assert sched.shape[0] == len(MODULI)
    assert (mods == np.array(MODULI)).all()
    assert 1 <= n_sub <= 3


@pytest.mark.parametrize("case", range(5))
def test_chip_smoke_kernel_cases_exact_in_interpret(case):
    """`chip_smoke.py`'s kernel phase at decode rows, in interpret mode: the
    served launch variants at smollm-135m widths equal the exact integer
    product (float epilogues via `chip_smoke.exact_f32`, emitted residues
    via `ref.rns_emit_ref`)."""
    import chip_smoke

    name, fn, args, check = list(chip_smoke.kernel_cases(4))[case]
    assert check(fn(*args)) is None, name


def test_exact_f32_is_the_kernel_float_epilogue():
    """Above 2^24 the kernel's float32 differs from a direct conversion;
    `exact_f32` replays its limb recombination."""
    import chip_smoke
    from repro.core import multiword as mw

    y = np.array([0, 1, -1, 2 ** 24 + 1, -(2 ** 31 - 1), 3 * 2 ** 40 + 12345])
    limbs = [jnp.asarray((np.abs(y) >> (15 * l)) & 0x7FFF, jnp.int32)
             for l in range(5)]
    mag = np.asarray(mw.limbs_to_float(limbs))
    assert np.array_equal(chip_smoke.exact_f32(y),
                          np.where(y < 0, -mag, mag))
