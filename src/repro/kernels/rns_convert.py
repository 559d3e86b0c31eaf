"""Pallas TPU kernels for the RNS conversion boundary (DESIGN.md §10).

Two kernels close the last off-Pallas gap in the ``rns_dense`` hot path —
conversion endpoints used to bail to sequential jnp even under
``backend="pallas"``:

  rns_forward — binary → residue planes: one broadcast mod per block,
                (1, S) int32 × (C, 1) moduli → (C, S) canonical residues.
  rns_reverse — the fused MRC reverse converter.  One VMEM-resident pass per
                block performs
                  ① digit extraction, vectorized over the (j, i) triangular
                    schedule as nested `fori_loop`s reading the dense (k, k)
                    inverse table from SMEM (the old converter unrolled ~k²/2
                    Python-loop steps with per-pair host constants),
                  ② limb-Horner recombination in 15-bit limbs (int32-safe,
                    no int64 anywhere — DESIGN.md §8.2),
                  ③ signed-range correction against ⌈M/2⌉,
                  ④ float32 dequantization, optionally fused with a
                    broadcast scale.

Both kernels are bit-identical to their `ConversionPlan` jnp twins: digit
extraction is exact integer arithmetic, and the sign-correction/float
recombination epilogue CALLS the shared `core/multiword.py` helpers on
values read from the limb scratch (only the Horner step is inlined — its
modulus arrives traced from SMEM, which `limbs_horner`'s static-int
signature cannot express).  Layout: the element axis is flattened and
blocked; the whole channel axis (k ≤ 12) and limb axis (≤ 5) stay resident
per block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import multiword as mw
from repro.core.channel_plan import resolve_interpret
from repro.core.conversion_plan import ConversionPlan
from repro.core.multiword import LIMB_BITS, LIMB_MASK

__all__ = ["rns_forward", "rns_reverse"]


# ----------------------------------------------------------------- forward --
# Bytes of the (C, rows, L) int32 output block one forward step writes: the
# row block is sized to this, in multiples of the int8 sublane tile.
_FORWARD_BLOCK_BYTES = 2 * 1024 * 1024


def _forward_kernel(mods_ref, x_ref, o_ref):
    # one (rows, L) block against each SMEM modulus — one VPU mod per channel
    x = x_ref[...].astype(jnp.int32)
    for c in range(o_ref.shape[0]):
        o_ref[c, :, :] = jnp.mod(x, mods_ref[c])


@functools.partial(jax.jit, static_argnames=("moduli", "interpret"))
def rns_forward(x, moduli: tuple, *, interpret: bool | None = None):
    """Binary → residues: (…,) int → (C, …) canonical int32 residues.

    Kernel twin of ``conversion_plan.forward(backend="jnp")``; negative
    inputs map to the coset representative.  Returns int32 — callers pick the
    residue dtype (the cast is free inside the surrounding jit).

    The input keeps its trailing axis as the lane axis: leading axes fold
    into rows, which are blocked in multiples of 32 (the int8 sublane tile),
    so a stacked (layers, K, N) weight converts without relayout.

    This is also the encode-time converter (`rns_tensor.encode` /
    `RNSTensor.from_int8` with ``backend="pallas"``): once a weight's
    residues are built here, no conversion kernel runs for it again — the
    matmul entry points accept the pre-converted stack as-is (DESIGN.md §12).
    """
    mods = tuple(int(m) for m in moduli)
    C = len(mods)
    shape = x.shape
    L = shape[-1] if x.ndim else 1
    x2 = x.reshape(-1, L)
    R = x2.shape[0]
    rows = max(32, _FORWARD_BLOCK_BYTES // (4 * C * L) // 32 * 32)
    b = min(rows, R)
    pad = (-R) % b
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    interpret = resolve_interpret(interpret)
    out = pl.pallas_call(
        _forward_kernel,
        grid=((R + pad) // b,),
        in_specs=[
            pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((b, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((C, b, L), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((C, R + pad, L), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)) if not interpret else None,
        interpret=interpret,
    )(jnp.asarray(mods, jnp.int32), x2)
    return out[:, :R].reshape((C,) + shape)


# ----------------------------------------------------------------- reverse --
def _reverse_kernel(inv_ref, mods_ref, r_ref, *rest,
                    plan: ConversionPlan, with_scale: bool):
    if with_scale:
        scale_ref, o_ref, dig_ref, acc_ref = rest
    else:
        o_ref, dig_ref, acc_ref = rest
    k, L = plan.k, plan.nlimbs

    # ① MRC digit extraction over the (j, i) triangular schedule.  The inner
    # loop runs a fixed k−1 trip count with an i<j mask (inv is zero-padded
    # above the diagonal, and dig_ref rows ≥ j still hold residues < m, so
    # the masked lanes never overflow) — static trip counts, no Python
    # unrolling, one SMEM table read per step.  d_i < m_i may exceed m_j, so
    # the single +m_j correction only bounds |u| < max(m_i, m_j) and the
    # FLOORED jnp.mod canonicalizes a still-negative product (same op
    # sequence as the jnp twin); |u·inv| < max(m_i, m_j)·m_j ≤ 2^30.
    dig_ref[...] = r_ref[...]

    def digit_row(j, carry):
        mj = mods_ref[j]

        def pair(i, t):
            d = dig_ref[pl.ds(i, 1), :]
            u = t - d
            u = jnp.where(u < 0, u + mj, u)
            u = jnp.mod(u * inv_ref[j, i], mj)
            return jnp.where(i < j, u, t)

        t = jax.lax.fori_loop(0, k - 1, pair, dig_ref[pl.ds(j, 1), :])
        dig_ref[pl.ds(j, 1), :] = t
        return carry

    jax.lax.fori_loop(1, k, digit_row, 0)

    # ② Horner recombination x = d_0 + m_0(d_1 + m_1(d_2 + …)) in 15-bit
    # limbs: every product limb·m ≤ 2^15·2^15 plus digit and carry stays
    # int32-safe (the multiword.limbs_horner bound, m ≤ 2^15 validated by the
    # plan).
    acc_ref[...] = jnp.zeros_like(acc_ref)
    acc_ref[pl.ds(0, 1), :] = dig_ref[pl.ds(k - 1, 1), :]

    def horner(jj, carry):
        j = k - 2 - jj
        mj = mods_ref[j]
        c = dig_ref[pl.ds(j, 1), :]            # digit joins limb 0's carry-in
        for l in range(L):                     # static limb count ≤ 5
            v = acc_ref[pl.ds(l, 1), :] * mj + c
            acc_ref[pl.ds(l, 1), :] = jnp.bitwise_and(v, LIMB_MASK)
            c = jnp.right_shift(v, LIMB_BITS)
        return carry

    jax.lax.fori_loop(0, k - 1, horner, 0)

    # ③ + ④ signed-range correction and dequantization — the multiword
    # helpers run unchanged on values read from the scratch ref (elementwise
    # jnp ops), so the kernel structurally cannot drift from the jnp twin's
    # float32 op sequence.
    acc = [acc_ref[pl.ds(l, 1), :] for l in range(L)]
    is_neg = mw.limbs_ge_const(acc, plan.half)
    pos = mw.limbs_to_float(acc)
    neg = mw.limbs_to_float(mw.limbs_const_minus(plan.M, acc))
    val = jnp.where(is_neg, -neg, pos)
    if with_scale:
        val = val * scale_ref[...]
    o_ref[...] = val


@functools.partial(jax.jit, static_argnames=("plan", "block", "interpret"))
def rns_reverse(residues, plan: ConversionPlan, *, scale=None,
                block: int = 1024, interpret: bool | None = None):
    """Fused MRC reverse conversion: (C, …) canonical int32 residues →
    float32 signed values of shape (…).

    ``scale`` (optional) broadcasts against the output shape and fuses the
    dequant multiply into the kernel epilogue.  The element axis is flattened
    and blocked; the inverse table and moduli live in SMEM (scalar-indexed by
    the digit loops), digits and limb accumulators in VMEM scratch.  Padding
    lanes hold zero residues — their digits are zero and are sliced off.
    """
    C = residues.shape[0]
    if C != plan.k:
        raise ValueError(f"residues have {C} channels, plan has {plan.k}")
    shape = residues.shape[1:]
    r = residues.astype(jnp.int32).reshape(C, -1)
    S = r.shape[1]
    b = max(1, min(block, S))
    pad = (-S) % b
    if pad:
        r = jnp.pad(r, ((0, 0), (0, pad)))
    Sp = S + pad
    with_scale = scale is not None
    interpret = resolve_interpret(interpret)
    L = plan.nlimbs

    in_specs = [
        pl.BlockSpec((C, C), lambda i: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((C, b), lambda i: (0, i)),
    ]
    args = [jnp.asarray(plan.inv), jnp.asarray(plan.mods), r]
    if with_scale:
        s = jnp.broadcast_to(jnp.asarray(scale, jnp.float32),
                             shape).reshape(1, -1)
        if pad:
            s = jnp.pad(s, ((0, 0), (0, pad)))
        in_specs.append(pl.BlockSpec((1, b), lambda i: (0, i)))
        args.append(s)
    out = pl.pallas_call(
        functools.partial(_reverse_kernel, plan=plan, with_scale=with_scale),
        grid=(Sp // b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Sp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, b), jnp.int32),
                        pltpu.VMEM((L, b), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)) if not interpret else None,
        interpret=interpret,
    )(*args)
    return out[0, :S].reshape(shape)
