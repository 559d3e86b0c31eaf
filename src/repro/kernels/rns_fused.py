"""The RNS linear-pipeline megakernel: Stage ②–⑤ in ONE `pallas_call`.

`rns_fused_matmul` executes the *entire* integer linear pipeline —

  Stage ② (operand preparation)   → activation int8 quantization (optional,
                                    the `rns_dense` datapath: round/clip/cast
                                    happen per VMEM block, the (M, K) int8
                                    activation tensor never exists in HBM)
                                    and weight forward conversion (per-channel
                                    `|w|_m` of the raw int8 block — or a
                                    no-op for pre-encoded
                                    :class:`~repro.core.rns_tensor.RNSTensor`
                                    residues);
  Stage ③ (carry-save accumulation) → per-channel int8 MXU dots accumulated
                                    across the K grid dimension into a
                                    `(C, bm, bn)` int32 VMEM scratch — all C
                                    channel accumulators for the output tile
                                    stay resident, *zero* reduction in the
                                    K loop;
  Stage ④ (squeezing + final add) → the shared fold ladder
                                    (`ChannelPlan.fold`, signed broadcast
                                    mode) once per tile on the last K step;
  Stage ⑤ (reverse conversion)    → MRC digit extraction over the triangular
                                    inverse-table schedule, 15-bit limb-Horner
                                    recombination, signed-range correction and
                                    the dequant multiplies — all still inside
                                    the same kernel invocation, on values that
                                    never left VMEM

— inside one grid over (M, N) output tiles with a sequential K loop.  The
staged ``backend="pallas"`` pipeline launches `rns_forward`, `rns_matmul`,
and `rns_reverse` separately, so the `(C, M, N)` int32 residue tensor (C×
larger than the f32 output) makes two full HBM round-trips between stages;
here it is a VMEM scratch and the only HBM traffic is the operands in and
the f32 output tile out — the paper's defer-everything principle applied to
the memory system, not just the adder tree (DESIGN.md §13).

Bit-identity: every stage replays the exact op sequence of its staged twin —
the quantizer's round/clip formula (`core/quant.py`), `ChannelPlan.fold` on
schedule rows streamed exactly as `kernels/rns_matmul.py` streams them, and
the `rns_reverse` digit/limb/float epilogue (integer steps are exact, the
float recombination and scale multiplies run in the same order) — so
``pallas_fused`` output is bit-identical to both staged backends on every
golden (`tests/test_kernels.py`).

Tiling comes from `kernels/tune.blocks_for`: a row of the committed tune
table, or a static default — never a sweep at trace time.  The row extent is
padded up to the int8 sublane tile (`tune.clip_blocks`), so decode launches
of a few rows still run tile-aligned blocks.  The ConversionPlan moduli and
inverse tables ride along as SMEM operands; the fold rungs are baked in from
the static plan, or — for a channel-sharded launch, whose per-device rungs
are traced — streamed as a (C, R, 2) VMEM table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import multiword as mw
from repro.core.channel_plan import ChannelPlan, resolve_interpret
from repro.core.conversion_plan import ConversionPlan
from repro.core.multiword import LIMB_BITS, LIMB_MASK
from repro.core.quant import QMAX
from repro.core.rns import basis_for_int8_matmul
from repro.core.rns_tensor import RNSTensor

__all__ = ["rns_fused_matmul", "rns_fused_crt_partial"]

# the `pallas_call`'s own name: a compiled program holds the kernel as the
# custom calls `_fused_call.<n>`, the name the benchmark's roofline reader
# matches in the device trace, whatever the Python wrapper is called
KERNEL_NAME = "_fused_call"


def _kernel(*refs, plan: ChannelPlan, conv: ConversionPlan, nk: int,
            quantize: bool, residue_in: bool, has_gate: bool, emit: bool,
            has_srow: bool, has_scol: bool, has_scale: bool, encoded: bool,
            crt: bool, nlimbs_out: int):
    rest = list(refs)
    # Fold rungs: a channel-sharded (crt) launch streams its per-device
    # rungs as a traced (C, R, 2) table; every other launch bakes the static
    # plan's rungs into the kernel (shift/multiply by constants).
    sched_ref = rest.pop(0) if crt else None
    mods_ref = rest.pop(0)
    inv_ref = rest.pop(0)
    x_ref = rest.pop(0)
    srow_ref = rest.pop(0) if has_srow else None
    gate_ref = rest.pop(0) if has_gate else None
    w_ref = rest.pop(0)
    scol_ref = rest.pop(0) if has_scol else None
    scale_ref = rest.pop(0) if has_scale else None
    creq_ref = rest.pop(0) if emit else None
    crt_v_ref = rest.pop(0) if crt else None
    crt_mc_ref = rest.pop(0) if crt else None
    o_ref, acc_ref = rest
    C = plan.k
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Stage ② activations: the quantizer's exact round/clip formula
    # (core/quant.py) on the raw block — the int8 activation tensor is never
    # materialized in HBM.  Padding rows divide by a 1.0 pad scale (never 0).
    # Residue-in activations (the chained datapath, DESIGN.md §14) skip
    # Stage ② entirely: the operand already IS the (C, bm, bk) canonical
    # residue stack of an activation RNSTensor, sliced per channel below.
    if quantize:
        a = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32)
                               / srow_ref[...]), -QMAX, QMAX)
        a = a.astype(jnp.int8)
    elif residue_in:
        a = None
    else:
        a = x_ref[...]
    if a is not None and plan.residue_dtype != jnp.int8:
        a = a.astype(plan.residue_dtype)     # wide-residue bases (m > 128)

    # Stage ② weights + Stage ③: per-channel forward conversion (live int8
    # weights) feeding the MXU contraction — no reduction inside the K loop.
    # Pre-encoded residues skip the mod entirely (the encode-once datapath).
    for c in range(C):
        if residue_in:
            ac = x_ref[c, :, :]
            if has_gate:
                # The gate's per-channel modular multiply, fused into the
                # prologue: |q_u·q_g|_m from the raw int8 gate block — both
                # factors < m ≤ 2^15, the product < 2^30, so one direct
                # floored mod is int32-exact and equals `channel_plan.modmul`
                # canonically (integer identity, tests/test_chain.py).
                g = jnp.mod(gate_ref[...].astype(jnp.int32), mods_ref[c])
                ac = jnp.mod(ac.astype(jnp.int32) * g,
                             mods_ref[c]).astype(plan.residue_dtype)
        else:
            ac = a
        if encoded:
            b = w_ref[c, :, :]
        else:
            b = jnp.mod(w_ref[...].astype(jnp.int32),
                        mods_ref[c]).astype(plan.residue_dtype)
        acc_ref[c, :, :] = acc_ref[c, :, :] + jax.lax.dot_general(
            ac, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @pl.when(k_step == nk - 1)
    def _epilogue():
        if crt:
            # CRT-partial epilogue for channel-sharded launches (repro.dist,
            # DESIGN.md §17): this launch holds only a SLICE of the basis, so
            # the MRC digit schedule (which couples all channels) cannot run.
            # Instead emit the CRT partial sum Σ_j |r_j·v_j|_{m_j}·(M/m_j)
            # over the LOCAL channels as 15-bit limb planes: the psum of
            # these planes over shards equals the full CRT sum, < C·M, which
            # the (replicated) finish reduces mod M to the SAME canonical
            # value the single-device MRC epilogue recombines.  Int32 safety:
            # r, v_j < 2^15 so r·v_j < 2^30; α_j < 2^15, each mc limb < 2^15
            # so α_j·mc < 2^30; running limb + carry keep v < 2^31 with the
            # carry propagated after EVERY channel add.
            limbs = [jnp.zeros(acc_ref.shape[1:], jnp.int32)
                     for _ in range(nlimbs_out)]
            for j in range(C):
                r = plan.fold(acc_ref[j, :, :], sched=sched_ref[j, :, :],
                              m=mods_ref[j])
                alpha = jnp.mod(r * crt_v_ref[j], mods_ref[j])
                carry = jnp.zeros(acc_ref.shape[1:], jnp.int32)
                nxt = []
                for l in range(nlimbs_out):
                    v = limbs[l] + crt_mc_ref[j, l] * alpha + carry
                    nxt.append(jnp.bitwise_and(v, LIMB_MASK))
                    carry = jnp.right_shift(v, LIMB_BITS)
                limbs = nxt
            for l in range(nlimbs_out):
                o_ref[l, :, :] = limbs[l]
            return

        # Stage ④: the shared fold ladder per channel, on schedule rows
        # streamed exactly as kernels/rns_matmul.py streams them; signed
        # (broadcast-operand) plans fold |acc| with the sign fix-up.  The
        # (C, bm, bn) canonical residues live only in this kernel's values —
        # they never touch HBM.
        # Stage ⑤ digits: the MRC triangular schedule over the SMEM inverse
        # table — same op order (and the same floored-mod canonicalization
        # of a still-negative product) as ConversionPlan's twins.
        digits = []
        for j in range(C):
            t = plan.fold(acc_ref[j, :, :], j, m=mods_ref[j])
            mj = mods_ref[j]
            for i in range(j):
                t = t - digits[i]
                t = jnp.where(t < 0, t + mj, t)
                t = jnp.mod(t * inv_ref[j, i], mj)
            digits.append(t)

        # Limb-Horner recombination in 15-bit limbs (int32-safe, no int64 —
        # the multiword bound, m ≤ 2^15 validated by the plan), then the
        # shared signed-range correction / float recombination helpers.
        L = conv.nlimbs
        acc = mw.limbs_from_scalar(digits[C - 1], L)
        for j in range(C - 2, -1, -1):
            mj = mods_ref[j]
            carry = digits[j]                  # digit joins limb 0's carry-in
            nxt = []
            for l in range(L):
                v = acc[l] * mj + carry
                nxt.append(jnp.bitwise_and(v, LIMB_MASK))
                carry = jnp.right_shift(v, LIMB_BITS)
            acc = nxt
        is_neg = mw.limbs_ge_const(acc, conv.half)
        pos = mw.limbs_to_float(acc)
        neg = mw.limbs_to_float(mw.limbs_const_minus(conv.M, acc))
        val = jnp.where(is_neg, -neg, pos)

        if emit:
            # In-domain requantize (DESIGN.md §14): scale the exact integer
            # product back into ±127 by BOUND — q' = clip(round(t/c), ±QMAX)
            # with t = y·s_col and c = requant_const(s_col, K) streamed as an
            # SMEM scalar (|t| ≤ c·127, so the clip never loses information)
            # — then re-encode the canonical residues per channel.  The
            # activation never leaves the domain in HBM: the output block IS
            # the next launch's residue operand, and its (M, 1) dequant
            # scale s_row·c is reconstructed outside from the same values
            # (`quant.requant_scale` — one source, bit-matched to the
            # dequant→requantize the unchained reference replays).
            t = val * scol_ref[...]
            q = jnp.clip(jnp.round(t / creq_ref[0]), -QMAX, QMAX)
            q32 = q.astype(jnp.int32)
            for j in range(C):
                o_ref[j, :, :] = jnp.mod(q32, mods_ref[j]).astype(
                    plan.residue_dtype)
            return

        # Fused dequant.  Order matters for bit-parity: (y · s_row) · s_col
        # is the seed-golden-pinned sequence of the staged rns_dense
        # epilogue; a generic `scale` replays `reverse(scale=...)`'s single
        # broadcast multiply (lowered to the row/col/full operand that
        # matches its broadcast shape — at most one of the three fires).
        if has_srow:
            val = val * srow_ref[...]
        if has_scol:
            val = val * scol_ref[...]
        if has_scale:
            val = val * scale_ref[...]
        o_ref[...] = val


@functools.partial(
    jax.jit, static_argnames=("plan", "conv", "quantize", "residue_in",
                              "has_gate", "emit", "has_srow", "has_scol",
                              "has_scale", "encoded", "bm", "bn", "bk",
                              "interpret", "crt", "nlimbs_out"))
def _fused_call(x, srow, gate, w, scol, scale, creq, *, plan: ChannelPlan,
                conv: ConversionPlan, quantize: bool, residue_in: bool,
                has_gate: bool, emit: bool, has_srow: bool,
                has_scol: bool, has_scale: bool, encoded: bool, bm: int,
                bn: int, bk: int, interpret: bool,
                sched_tab=None, mods_tab=None, crt_v=None, crt_mc=None,
                crt: bool = False, nlimbs_out: int = 0):
    C = plan.k
    M, K = x.shape[-2], x.shape[-1]
    N = w.shape[-1]
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        # pad residues/gate with 0 — the canonical residue of 0, inert in
        # the contraction and under the gate's modular multiply
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, pm), (0, pk)))
    if has_srow and pm:
        # pad rows quantize as 0/1.0 = 0 — never a 0/0 NaN lane
        srow = jnp.pad(srow, ((0, pm), (0, 0)), constant_values=1.0)
    if has_gate and (pm or pk):
        gate = jnp.pad(gate, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, 0),) * (w.ndim - 2) + ((0, pk), (0, pn)))
    if has_scol and pn:
        scol = jnp.pad(scol, ((0, 0), (0, pn)))
    if has_scale and (pm or pn):
        scale = jnp.pad(scale, ((0, pm), (0, pn)))
    Mp, Np, Kp = M + pm, N + pn, K + pk
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    # mods default to the STATIC plan table; a channel-sharded launch
    # (repro.dist) overrides it and the fold rungs with TRACED shard_map
    # operands — the local plan is SPMD-uniform (shapes only), the actual
    # per-device moduli and rungs arrive sliced over the mesh.
    in_specs, args = [], []
    if crt:
        in_specs.append(pl.BlockSpec((C, plan.num_rungs, 2),
                                     lambda i, j, k: (0, 0, 0)))
        args.append(sched_tab)
    in_specs += [
        pl.BlockSpec((C,), lambda i, j, k: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((C, C), lambda i, j, k: (0, 0),
                     memory_space=pltpu.SMEM),
    ]
    args += [jnp.asarray(plan.mods) if mods_tab is None else mods_tab,
             jnp.asarray(conv.inv)]
    if residue_in:
        in_specs.append(pl.BlockSpec((C, bm, bk), lambda i, j, k: (0, i, k)))
    else:
        in_specs.append(pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)))
    args.append(x)
    if has_srow:
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)))
        args.append(srow)
    if has_gate:
        in_specs.append(pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)))
        args.append(gate)
    if encoded:
        in_specs.append(pl.BlockSpec((C, bk, bn), lambda i, j, k: (0, k, j)))
    else:
        in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)))
    args.append(w)
    if has_scol:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(scol)
    if has_scale:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        args.append(scale)
    if emit:
        in_specs.append(pl.BlockSpec((1,), lambda i, j, k: (0,),
                                     memory_space=pltpu.SMEM))
        args.append(creq)
    if crt:
        in_specs.append(pl.BlockSpec((C,), lambda i, j, k: (0,),
                                     memory_space=pltpu.SMEM))
        args.append(crt_v)
        in_specs.append(pl.BlockSpec((C, nlimbs_out),
                                     lambda i, j, k: (0, 0),
                                     memory_space=pltpu.SMEM))
        args.append(crt_mc)

    if emit:
        out_spec = pl.BlockSpec((C, bm, bn), lambda i, j, k: (0, i, j))
        out_shape = jax.ShapeDtypeStruct((C, Mp, Np), plan.residue_dtype)
    elif crt:
        out_spec = pl.BlockSpec((nlimbs_out, bm, bn),
                                lambda i, j, k: (0, i, j))
        out_shape = jax.ShapeDtypeStruct((nlimbs_out, Mp, Np), jnp.int32)
    else:
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((Mp, Np), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, plan=plan, conv=conv, nk=nk,
                          quantize=quantize, residue_in=residue_in,
                          has_gate=has_gate, emit=emit, has_srow=has_srow,
                          has_scol=has_scol, has_scale=has_scale,
                          encoded=encoded, crt=crt, nlimbs_out=nlimbs_out),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((C, bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary")) if not interpret else None,
        interpret=interpret,
        name=KERNEL_NAME,
    )(*args)
    return out[:, :M, :N] if (emit or crt) else out[:M, :N]


def rns_fused_matmul(x, w, basis=None, *, quantize: bool = False,
                     gate=None, emit: str = "float",
                     scale_row=None, scale_col=None, scale=None,
                     requant_creq=None,
                     block_m: int | None = None, block_n: int | None = None,
                     block_k: int | None = None,
                     interpret: bool | None = None):
    """One-launch Stage ②–⑤ pipeline: (M, K) × (K, N) → float32 (M, N).

    ``x`` is (M, K): raw signed int8 activations (the broadcast-operand
    datapath — every channel's dot streams the same block), or, with
    ``quantize=True``, the raw float activations plus their per-row quant
    scale ``scale_row`` (the `rns_dense` datapath: round/clip/cast run
    per VMEM block and the scale is re-used for the dequant epilogue), or
    an *activation* :class:`~repro.core.rns_tensor.RNSTensor` (residues
    (C, M, K)) — the residue-in chained datapath (DESIGN.md §14): Stage ②
    is skipped entirely and ``scale_row`` defaults to the carried scale.
    Residue-in launches may fuse an elementwise modular ``gate`` — a raw
    (M, K) int8 gate factor multiplied per channel in the prologue.

    ``w`` is the weight operand in any of the three forms the staged
    pipeline accepts: a raw (K, N) int8 matrix (forward-converted to
    residues per block, in VMEM), a pre-encoded
    :class:`~repro.core.rns_tensor.RNSTensor`, or its raw (C, K, N)
    canonical residue stack.

    ``emit`` selects the epilogue: ``"float"`` runs the MRC reverse +
    dequant and returns a float32 (M, N); ``"residues"`` requantizes the
    exact integer product in-domain (`quant.requant_const` rule, needs
    ``scale_row``/``scale_col``) and returns an activation RNSTensor whose
    (C, M, N) residues feed the next residue-in launch — no MRC exit.

    Dequant epilogue (all optional, fused into the kernel): ``scale_row``
    (M, 1) then ``scale_col`` (1, N) — the staged `rns_dense` op order
    ``(y · sx) · sw`` — or a generic ``scale`` broadcast against (M, N)
    (the staged ``reverse(scale=...)`` single multiply).

    Block sizes default to the autotuner (`kernels/tune.blocks_for`);
    explicit ``block_*`` always win.  Output is bit-identical to the staged
    ``backend="pallas"`` (and ``"jnp"``) pipeline for any tiling: the
    integer stages are exact and the float epilogue replays the staged op
    order.
    """
    from . import tune
    from repro.core.quant import requant_const

    if emit not in ("float", "residues"):
        raise ValueError(f"emit must be 'float' or 'residues', got {emit!r}")
    emit_res = emit == "residues"

    encoded = isinstance(w, RNSTensor)
    if encoded:
        if w.residues.ndim != 3:
            raise ValueError("rns_fused_matmul needs an unbatched (C, K, N) "
                             f"encoded weight, got {w.residues.shape}")
        if w.bound > 128:
            raise ValueError(f"encoded weight bound {w.bound} exceeds the "
                             "int8 operand range the basis is sized for")
        if basis is not None and tuple(basis.moduli) != w.moduli:
            raise ValueError(f"basis {basis.moduli} does not match encoded "
                             f"weight channels {w.moduli}")
        basis = w.basis
        w_arr = w.residues
    else:
        w_arr = w

    residue_in = isinstance(x, RNSTensor)
    if residue_in:
        if x.residues.ndim != 3:
            raise ValueError("rns_fused_matmul needs an unbatched (C, M, K) "
                             f"activation RNSTensor, got {x.residues.shape}")
        if quantize:
            raise ValueError("quantize=True is the float-activation prologue;"
                             " a residue-in RNSTensor is already quantized")
        if x.bound > 128:
            raise ValueError(f"activation bound {x.bound} exceeds the int8 "
                             "operand range the basis is sized for")
        if basis is not None and tuple(basis.moduli) != x.moduli:
            raise ValueError(f"basis {basis.moduli} does not match activation "
                             f"channels {x.moduli}")
        basis = x.basis
        x_arr = x.residues
        if scale_row is None:
            scale_row = x.scale
    else:
        x_arr = x
    if gate is not None:
        if not residue_in:
            raise ValueError("gate= fuses into the residue-in prologue; "
                             "float/int8 activations gate before quantize")
        gate = jnp.asarray(gate)
        if gate.shape != x_arr.shape[-2:]:
            raise ValueError(f"gate {gate.shape} must match the (M, K) "
                             f"activation block {x_arr.shape[-2:]}")
        if emit_res:
            raise ValueError("gate= with emit='residues' is unsupported: the "
                             "requantize bound is sized for K·127², not the "
                             "gated K·127³ product")
    M, K = x_arr.shape[-2], x_arr.shape[-1]
    if basis is None:
        if w_arr.ndim == 3:
            raise ValueError("raw (C, K, N) residues need an explicit basis")
        basis = basis_for_int8_matmul(K)
    moduli = tuple(int(m) for m in basis.moduli)
    conv = ConversionPlan.for_basis(basis)
    if not conv.device_reversible:
        raise ValueError(
            f"moduli {moduli} exceed the int32 limb-Horner bound "
            f"m <= {mw.MAX_HORNER_MODULUS}; the fused kernel cannot host "
            "this basis")
    # Residue-in operands are CANONICAL (both factors in [0, m)), so the fold
    # plan is unsigned — K·(m−1)² per-channel bound instead of the signed
    # broadcast-operand K·128·(m−1) bound.
    plan = ChannelPlan.for_matmul(moduli, K, signed=not residue_in)
    if w_arr.ndim == 3:
        if w_arr.shape[0] != plan.k:
            raise ValueError(f"residue stack has {w_arr.shape[0]} channels, "
                             f"basis has {plan.k}")
        encoded = True
        w_arr = w_arr.astype(plan.residue_dtype)     # no-op by the dtype rule
    if residue_in:
        x_arr = x_arr.astype(plan.residue_dtype)
    if quantize and scale_row is None:
        raise ValueError("quantize=True needs the per-row quant scale_row")
    if scale_row is not None and not (quantize or residue_in or emit_res):
        raise ValueError("scale_row is the quantize-mode row scale; int8 "
                         "inputs fuse dequant via scale= instead")
    if scale is not None and (scale_row is not None or scale_col is not None):
        raise ValueError("pass either scale or scale_row/scale_col, not both")
    if emit_res:
        if scale_col is None:
            raise ValueError("emit='residues' needs scale_col: the in-domain "
                             "requantize constant is max(scale_col)·K·127")
        if scale_row is None:
            raise ValueError("emit='residues' needs scale_row (or a carried "
                             "activation scale) to form the output scale")
        if scale is not None:
            raise ValueError("emit='residues' uses scale_row/scale_col; "
                             "generic scale= has no in-domain meaning")
    if requant_creq is not None and not emit_res:
        raise ValueError("requant_creq= overrides the in-domain requantize "
                         "constant and only means something with "
                         "emit='residues'")
    N = w_arr.shape[-1]

    interpret = resolve_interpret(interpret)
    variant = ("pallas_fused" + ("_res" if residue_in else "")
               + ("_emit" if emit_res else ""))
    if block_m is None or block_n is None or block_k is None:
        tbm, tbn, tbk = tune.blocks_for(M, K, N, plan.k,
                                        dtype=str(w_arr.dtype),
                                        backend=variant,
                                        x_channels=residue_in, emit=emit_res)
        block_m, block_n, block_k = (block_m or tbm, block_n or tbn,
                                     block_k or tbk)
    bm, bn, bk = tune.clip_blocks((block_m, block_n, block_k), M, K, N)

    srow = (jnp.asarray(scale_row, jnp.float32).reshape(M, 1)
            if scale_row is not None else None)
    scol = (jnp.asarray(scale_col, jnp.float32).reshape(1, N)
            if scale_col is not None else None)
    sc = None
    if scale is not None:
        # Lower the generic scale to the cheapest operand its broadcast
        # shape admits — a full (M, N) stream costs HBM traffic equal to
        # the output, so row/col/scalar scales ride the tiny specs instead
        # (the multiply itself is elementwise either way: same bits as the
        # staged reverse(scale=...) broadcast).
        s = jnp.asarray(scale, jnp.float32)
        bshape = jnp.broadcast_shapes(s.shape, (M, N))
        if bshape != (M, N):
            raise ValueError(f"scale {s.shape} does not broadcast "
                             f"against the ({M}, {N}) output")
        s2 = s.reshape((1,) * (2 - s.ndim) + s.shape) if s.ndim < 2 else s
        if s2.shape[0] == 1:                     # scalar / (N,) / (1, N)
            scol = jnp.broadcast_to(s2, (1, N))
        elif s2.shape[1] == 1:                   # (M, 1)
            srow = jnp.broadcast_to(s2, (M, 1))
        else:
            sc = jnp.broadcast_to(s2, (M, N))

    creq = out_scale = None
    if emit_res:
        # A column-sharded launch (repro.dist) sees only an N/n column slice
        # of scale_col, but the requantize constant is max over the FULL
        # column scale — the wrapper computes it once outside the shard_map
        # region and overrides it here so every shard divides by the same c.
        creq = (requant_const(scale_col, K) if requant_creq is None
                else jnp.asarray(requant_creq, jnp.float32).reshape(()))
        # The output scale is formed OUTSIDE the kernel from the same values
        # the epilogue divides by — `quant.requant_scale(srow, scol, K)`
        # spelled on the already-reshaped operands (same float ops, one rule).
        out_scale = srow * creq
    kernel_srow = srow if (quantize or not emit_res) else None
    out = _fused_call(x_arr, kernel_srow, gate, w_arr, scol, sc,
                      creq.reshape(1) if creq is not None else None,
                      plan=plan, conv=conv, quantize=quantize,
                      residue_in=residue_in, has_gate=gate is not None,
                      emit=emit_res, has_srow=kernel_srow is not None,
                      has_scol=scol is not None, has_scale=sc is not None,
                      encoded=encoded, bm=bm, bn=bn, bk=bk,
                      interpret=interpret)
    # The launch boundary is a bit-exactness contract (batch invariance,
    # sharded == single-device parity), so it must be opaque to consumer
    # fusion: off-TPU the interpreted kernel inlines into the surrounding
    # HLO, where XLA duplicates the dequant epilogue per consumer and
    # FMA-contracts the copies differently — the same launch then yields
    # different bits depending on what reads it.  The barrier pins ONE
    # materialization of the declared output (an identity on its value).
    out = jax.lax.optimization_barrier(out)
    if emit_res:
        return RNSTensor(residues=out, scale=out_scale, basis=basis,
                         bound=127, signed=True)
    return out


def rns_fused_crt_partial(x, w, *, plan: ChannelPlan, conv: ConversionPlan,
                          mods, sched, crt_v, crt_mc,
                          quantize: bool = False, scale_row=None, gate=None,
                          block_m: int | None = None,
                          block_n: int | None = None,
                          block_k: int | None = None,
                          interpret: bool | None = None):
    """Channel-slice megakernel launch: Stage ②–④ + a CRT-partial epilogue.

    The channel-sharded distributed layout (`repro.dist.rns_shard`,
    DESIGN.md §17) gives every device a C/n slice of the residue stacks.
    MRC cannot run on a slice (its digit schedule couples all channels), so
    this entry replaces Stage ⑤ with the CRT partial sum over the LOCAL
    channels, Σ_j |r_j·v_j|_{m_j}·(M/m_j), returned as ``(L1, M, N)`` int32
    15-bit limb planes (``L1 = crt_mc.shape[-1]``).  One ``psum`` of the
    planes and a replicated mod-M finish recover the exact canonical value —
    the caller owns both; residues never leave the kernel.

    shard_map runs ONE program on every shard, so ``plan``/``conv`` are the
    SPMD-uniform *local-shaped* plan (device 0's slice with global bound and
    rung count — `repro.dist.rns_shard.local_plan`) while the actual
    per-device tables ride in as traced operands: ``mods`` (C,), ``sched``
    (C, R, 2), ``crt_v`` (C,) the CRT reconstruction inverses, ``crt_mc``
    (C, L1) the limb decompositions of M/m_j.

    ``x`` is a raw float (M, K) block with ``quantize=True``/``scale_row``
    (the dense prologue), a raw (C, M, K) canonical residue slice (the
    chained datapath — arrays, not RNSTensors: shard_map bodies hand slices
    around raw), or raw signed int8.  ``w`` is the (C, K, N) residue slice
    or a raw (K, N) int8 block (forward-converted against the sliced
    ``mods`` in-kernel).  ``gate`` fuses the residue-in modular gate.
    """
    from . import tune

    x = jnp.asarray(x)
    w = jnp.asarray(w)
    residue_in = x.ndim == 3
    encoded = w.ndim == 3
    if residue_in:
        x = x.astype(plan.residue_dtype)
        if x.shape[0] != plan.k:
            raise ValueError(f"residue slice has {x.shape[0]} channels, "
                             f"local plan has {plan.k}")
        if quantize:
            raise ValueError("quantize=True is the float prologue; residue "
                             "slices are already quantized")
    if encoded and w.shape[0] != plan.k:
        raise ValueError(f"weight slice has {w.shape[0]} channels, "
                         f"local plan has {plan.k}")
    if quantize and scale_row is None:
        raise ValueError("quantize=True needs the per-row quant scale_row")
    M, K = x.shape[-2], x.shape[-1]
    N = w.shape[-1]
    nlimbs_out = int(crt_mc.shape[-1])

    interpret = resolve_interpret(interpret)
    variant = "pallas_fused" + ("_res" if residue_in else "") + "_crt"
    if block_m is None or block_n is None or block_k is None:
        tbm, tbn, tbk = tune.blocks_for(M, K, N, plan.k, dtype=str(w.dtype),
                                        backend=variant,
                                        x_channels=residue_in)
        block_m, block_n, block_k = (block_m or tbm, block_n or tbn,
                                     block_k or tbk)
    bm, bn, bk = tune.clip_blocks((block_m, block_n, block_k), M, K, N)

    srow = (jnp.asarray(scale_row, jnp.float32).reshape(M, 1)
            if quantize else None)
    if gate is not None:
        gate = jnp.asarray(gate)
        if gate.shape != x.shape[-2:]:
            raise ValueError(f"gate {gate.shape} must match the (M, K) "
                             f"activation block {x.shape[-2:]}")
    return _fused_call(x, srow, gate, w, None, None, None,
                       plan=plan, conv=conv, quantize=quantize,
                       residue_in=residue_in, has_gate=gate is not None,
                       emit=False, has_srow=srow is not None,
                       has_scol=False, has_scale=False, encoded=encoded,
                       bm=bm, bn=bn, bk=bk, interpret=interpret,
                       sched_tab=jnp.asarray(sched, jnp.int32),
                       mods_tab=jnp.asarray(mods, jnp.int32),
                       crt_v=jnp.asarray(crt_v, jnp.int32),
                       crt_mc=jnp.asarray(crt_mc, jnp.int32),
                       crt=True, nlimbs_out=nlimbs_out)
