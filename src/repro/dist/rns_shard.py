"""shard_map wrappers for the fused RNS megakernel (DESIGN.md §17).

Two partitionings of ONE launch over the mesh's "model" axis:

channel (split C) — each device holds a C/n slice of the residue stacks and
  runs the CRT-partial megakernel entry
  (`kernels.rns_fused.rns_fused_crt_partial`): prologue + Stage ③ + its own
  fold ladder on the slice, emitting (L1, M, N) 15-bit limb planes of the
  partial CRT sum Σ_j |r_j·v_j|_{m_j}·(M/m_j).  ONE ``psum`` of those
  narrow planes — the only collective — then a replicated finish: limb
  carry propagation, ≤ C−1 conditional subtracts of M (the CRT sum is
  < C·M), truncation to the ConversionPlan limb count, and a bit-exact
  replay of the kernel's signed tail + pinned dequant order.  Residues
  never cross the interconnect: what crosses is the post-MRC reduced
  value.  ``emit="residues"`` launches REPLICATE instead (zero comms):
  per-channel re-encoding needs every device's moduli, and a replicated
  (C, M, N) output is exactly what the next channel-sharded launch's
  in_specs slice.

column (split N) — every device keeps the full basis and runs the
  unmodified megakernel on its N/n weight columns (bit-exact per column
  under any tiling), then the outputs all-gather along the column axis —
  the float (M, N), or the (C, M, N) residue slab for ``emit="residues"``
  (whose requantize constant is computed OUTSIDE from the full column
  scale and overrides the slice-local max via ``requant_creq``).

Bit-identity contract: integer stages are exact everywhere; the channel
finish reproduces the kernel epilogue's limb values (the CRT sum mod M and
the MRC recombination are the same canonical v < M — uniqueness of the
canonical residue) and replays its float op sequence; the column path runs
the single-device kernel per column slice.  `tests/test_dist.py` pins both
layouts against single-device greedy decode on an 8-device host mesh.

shard_map bodies may not close over tracers, so every traced value rides an
``ops`` dict with a matching spec dict; static plans/bases close over fine.
The local ChannelPlan is SPMD-uniform (`local_plan`): shard_map runs one
program on all shards, so only shapes/rung-counts are static — the actual
per-device moduli, fold schedules, and CRT tables arrive as sliced traced
operands.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import multiword as mw
from repro.core.channel_plan import ChannelPlan, residue_dtype_for
from repro.core.conversion_plan import ConversionPlan
from repro.core.quant import requant_const
from repro.core.rns import _modinv
from repro.core.rns_tensor import RNSTensor
from repro.kernels.rns_fused import rns_fused_crt_partial, rns_fused_matmul

from . import comms
from .context import current

__all__ = ["sharded_fused_matmul", "resolve_layout", "crt_tables", "local_plan"]


# ------------------------------------------------------------ CRT tables ---
@functools.lru_cache(maxsize=64)
def _crt_tables_cached(moduli):
    M = 1
    for m in moduli:
        M *= m
    nlimbs = mw.nlimbs_for(len(moduli) * M)
    v = np.asarray([_modinv(M // m, m) for m in moduli], np.int32)
    mc = np.asarray([mw.to_limbs_const(M // m, nlimbs) for m in moduli],
                    np.int32)
    return v, mc, nlimbs


def crt_tables(basis):
    """Per-channel CRT constants of a basis: ``(v, mc, L1)``.

    ``v[j] = |(M/m_j)^{-1}|_{m_j}`` (C,) int32 — the CRT reconstruction
    inverses — and ``mc[j] = limbs(M/m_j)`` (C, L1) int32 with
    ``L1 = nlimbs_for(C·M)``: the limb count sized for the un-reduced CRT
    sum Σ α_j·M_j < C·M (each α_j < m_j), which is what crosses the psum.
    """
    return _crt_tables_cached(tuple(int(m) for m in basis.moduli))


def local_plan(plan_g: ChannelPlan, nshards: int) -> ChannelPlan:
    """The SPMD-uniform local-shape plan for a channel-sharded launch.

    shard_map runs ONE program on every shard, so the static plan must be
    shard-independent: device 0's channel slice carries the right SHAPES
    (C/n channels, the globally-padded rung count) and the global
    bound/n_sub (extra conditional subtracts are no-ops on channels that
    need fewer), while each device's actual moduli/schedules ride in as
    traced operands.  Raises when the slices would disagree on the residue
    dtype — the single program casts every shard identically.
    """
    C = plan_g.k
    if C % nshards:
        raise ValueError(f"mesh 'model' size {nshards} does not divide the "
                         f"channel count C={C}; channel sharding needs "
                         "C % model == 0")
    Cl = C // nshards
    gdt = residue_dtype_for(plan_g.moduli)
    for i in range(nshards):
        sl = plan_g.moduli[i * Cl:(i + 1) * Cl]
        if residue_dtype_for(sl) != gdt:
            raise ValueError(
                f"channel slice {sl} selects residue dtype "
                f"{residue_dtype_for(sl)}, global basis selects {gdt}; the "
                "SPMD kernel must cast every shard identically")
    return dataclasses.replace(plan_g, moduli=plan_g.moduli[:Cl],
                               channels=plan_g.channels[:Cl],
                               rungs=plan_g.rungs[:Cl])


def _isolate(tree):
    """optimization_barrier around the sharded region's operands/results.

    Bit-identity with the single-device graph is per-LAUNCH: each sharded
    launch reproduces `rns_fused_matmul`'s bits exactly (verified at the
    kernel level).  But in a large jitted graph, XLA's fusion of the FLOAT
    ops around a launch (quantize's max-reduction, rms-norm means, …) can
    change when collectives appear in the graph — a 1-ulp drift that round/
    clip boundaries amplify into different greedy tokens.  Fencing the
    sharded region's inputs and outputs pins those neighbours to compile
    exactly as they do around an opaque single-device launch, restoring
    end-to-end bit-identity (tests/test_dist.py runs the whole Engine).
    """
    return jax.lax.optimization_barrier(tree)


# ------------------------------------------------------- channel layout ----
def _crt_finish(total, conv_g: ConversionPlan, C: int):
    """psum'ed limb planes → the kernel tail's exact float32.

    The summed CRT value Σ α_j·M_j is < C·M, so at most C−1 conditional
    subtracts of M reach the canonical v; post-psum limbs are < n·2^15
    (int32-safe), restored to 15-bit form first.  The truncated limbs then
    equal the single-device kernel's MRC accumulator bit-for-bit (canonical
    residue uniqueness: both are the little-endian 15-bit limbs of the same
    v < M), and the signed tail replays its float op sequence exactly.
    """
    ls = [total[i] for i in range(total.shape[0])]
    ls = mw._carry_propagate(ls)
    for _ in range(C - 1):
        ge = mw.limbs_ge_const(ls, conv_g.M)
        ls = mw.limbs_select(ge, mw.limbs_sub_const(ls, conv_g.M), ls)
    ls = ls[:conv_g.nlimbs]
    is_neg = mw.limbs_ge_const(ls, conv_g.half)
    pos = mw.limbs_to_float(ls)
    neg = mw.limbs_to_float(mw.limbs_const_minus(conv_g.M, ls))
    return jnp.where(is_neg, -neg, pos)


def _channel_call(ctx, x, w, basis, *, quantize, gate, srow, scol, sc,
                  interpret):
    ax, ndev = ctx.axis, ctx.nshards
    moduli = tuple(int(m) for m in basis.moduli)
    residue_in = isinstance(x, RNSTensor)
    x_arr = x.residues if residue_in else jnp.asarray(x)
    encoded = isinstance(w, RNSTensor) or jnp.asarray(w).ndim == 3
    w_arr = w.residues if isinstance(w, RNSTensor) else jnp.asarray(w)
    K = x_arr.shape[-1]

    plan_g = ChannelPlan.for_matmul(moduli, K, signed=not residue_in)
    lp = local_plan(plan_g, ndev)
    conv_g = ConversionPlan.for_basis(basis)
    conv_l = ConversionPlan.build(lp.moduli)
    crt_v, crt_mc, _ = crt_tables(basis)

    ops = {
        "x": x_arr, "w": w_arr,
        "mods": jnp.asarray(np.asarray(plan_g.mods), jnp.int32),
        "sched": jnp.asarray(np.asarray(plan_g.sched), jnp.int32),
        "crt_v": jnp.asarray(crt_v), "crt_mc": jnp.asarray(crt_mc),
    }
    specs = {
        "x": P(ax, None, None) if residue_in else P(None, None),
        "w": P(ax, None, None) if encoded else P(None, None),
        "mods": P(ax), "sched": P(ax, None, None),
        "crt_v": P(ax), "crt_mc": P(ax, None),
    }
    for name, v in (("srow", srow), ("gate", gate), ("scol", scol),
                    ("sc", sc)):
        if v is not None:
            ops[name] = jnp.asarray(v)
            specs[name] = P(*([None] * ops[name].ndim))

    def body(o):
        part = rns_fused_crt_partial(
            o["x"], o["w"], plan=lp, conv=conv_l, mods=o["mods"],
            sched=o["sched"], crt_v=o["crt_v"], crt_mc=o["crt_mc"],
            quantize=quantize, scale_row=o.get("srow") if quantize else None,
            gate=o.get("gate"), interpret=interpret)
        val = _crt_finish(jax.lax.psum(part, ax), conv_g, len(moduli))
        # the kernel epilogue's pinned dequant order: (y·s_row)·s_col[·s]
        if "srow" in o:
            val = val * o["srow"]
        if "scol" in o:
            val = val * o["scol"]
        if "sc" in o:
            val = val * o["sc"]
        return val

    return _isolate(jax.shard_map(body, mesh=ctx.mesh, in_specs=(specs,),
                                  out_specs=P(), check_vma=False)(
                                      _isolate(ops)))


def _gather_columns(res, ax, ndev):
    """Bit-exact tiled gather of per-device column slices along the last
    axis — `all_gather(..., tiled=True)` expressed as scatter-into-zeros +
    ``psum``.

    Not an optimisation: `lax.all_gather` inside a ``lax.scan`` body
    miscompiles on the XLA CPU backend (the gathered buffer aliases loop
    state — a launch that is bit-exact outside the scan returns garbage
    columns inside it, dependent on what else shares the body), and the
    8-device host mesh is this repo's reference parity platform
    (tests/test_dist.py).  ``psum`` in the same position is sound — the
    channel layout ships every decode step through it — so the gather is
    rebuilt on it: each device drops its slice into a zeros-elsewhere
    global-width buffer and the planes sum.  Every column has exactly ONE
    non-zero contributor, and floats ride bitcast to int32 so the identity
    ``x + 0`` is bitwise (a float -0.0 would round to +0.0 against a +0.0
    plane), making the emulation bit-identical to the tiled all_gather on
    every backend, not just equal in value.
    """
    i = jax.lax.axis_index(ax)
    nloc = res.shape[-1]
    f32 = res.dtype == jnp.float32
    plane = jax.lax.bitcast_convert_type(res, jnp.int32) if f32 else res
    buf = jnp.zeros(plane.shape[:-1] + (nloc * ndev,), plane.dtype)
    idx = (jnp.zeros((), jnp.int32),) * (plane.ndim - 1) + (i * nloc,)
    buf = jax.lax.dynamic_update_slice(buf, plane, idx)
    buf = jax.lax.psum(buf, ax)
    return jax.lax.bitcast_convert_type(buf, jnp.float32) if f32 else buf


# -------------------------------------------------------- column layout ----
def _column_call(ctx, x, w, basis, *, quantize, gate, emit, srow, scol, sc,
                 interpret):
    ax = ctx.axis
    emit_res = emit == "residues"
    residue_in = isinstance(x, RNSTensor)
    x_arr = x.residues if residue_in else jnp.asarray(x)
    x_meta = (x.bound, x.signed) if residue_in else None
    encoded = isinstance(w, RNSTensor) or jnp.asarray(w).ndim == 3
    w_arr = w.residues if isinstance(w, RNSTensor) else jnp.asarray(w)
    K = x_arr.shape[-1]

    ops = {"x": x_arr, "w": w_arr}
    specs = {
        "x": P(*([None] * x_arr.ndim)),          # activations replicate
        "w": P(None, None, ax) if encoded else P(None, ax),
    }
    if gate is not None:
        ops["gate"] = jnp.asarray(gate)
        specs["gate"] = P(None, None)
    if srow is not None:
        ops["srow"] = jnp.asarray(srow)
        specs["srow"] = P(None, None)            # (M, 1): rows replicate
    if scol is not None:
        ops["scol"] = jnp.asarray(scol)
        specs["scol"] = P(None, ax)              # (1, N): columns shard
    if sc is not None:
        ops["sc"] = jnp.asarray(sc)
        specs["sc"] = P(None, ax)                # (M, N) generic scale
    creq_g = out_scale = None
    if emit_res:
        # the requantize constant is max over the FULL column scale — a
        # slice-local max would diverge per shard and break bit-identity
        creq_g = requant_const(scol, K)
        out_scale = jnp.asarray(srow, jnp.float32) * creq_g
        ops["creq"] = creq_g
        specs["creq"] = P()

    def body(o):
        x_in = o["x"]
        if residue_in:
            x_in = RNSTensor(residues=x_in, scale=None, basis=basis,
                             bound=x_meta[0], signed=x_meta[1])
        out = rns_fused_matmul(
            x_in, o["w"], basis, quantize=quantize, gate=o.get("gate"),
            emit=emit, scale_row=o.get("srow"), scale_col=o.get("scol"),
            scale=o.get("sc"), requant_creq=o.get("creq"),
            interpret=interpret)
        res = out.residues if emit_res else out
        return _gather_columns(res, ax, ctx.nshards)

    out = jax.shard_map(body, mesh=ctx.mesh, in_specs=(specs,),
                        out_specs=P(), check_vma=False)(_isolate(ops))
    out = _isolate(out)
    if emit_res:
        return RNSTensor(residues=out, scale=out_scale, basis=basis,
                         bound=127, signed=True)
    return out


# ------------------------------------------------------------- dispatch ----
def resolve_layout(layout: str, nshards: int, basis, M: int, N: int, *,
                   emit: str = "float") -> str:
    """The partitioning ONE launch of ``basis`` with an (M, N) output takes
    on a model axis of ``nshards``: "channel", "column" or "replicate".

    ``layout="auto"`` asks the `comms` bytes-on-wire model; a forced layout
    is a preference with a per-launch feasibility fallback (preferred →
    other → replicate) on the divisibility of C resp. N."""
    moduli = tuple(int(m) for m in basis.moduli)
    C = len(moduli)
    lay = layout
    if lay == "auto":
        _, _, nlimbs = crt_tables(basis)
        lay = comms.choose_layout(
            C=C, M=M, N=N, nlimbs=nlimbs, ndev=nshards, emit=emit,
            itemsize=np.dtype(residue_dtype_for(moduli)).itemsize)
    if lay not in ("channel", "column", "replicate"):
        raise ValueError(f"unknown layout {lay!r}")
    if lay == "channel" and C % nshards:
        lay = "column" if N % nshards == 0 else "replicate"
    elif lay == "column" and N % nshards:
        lay = "channel" if C % nshards == 0 else "replicate"
    return lay


def sharded_fused_matmul(x, w, basis=None, *, ctx=None, layout=None,
                         quantize: bool = False, gate=None,
                         emit: str = "float", scale_row=None, scale_col=None,
                         scale=None, interpret: bool | None = None):
    """Distribution-aware twin of `kernels.rns_fused.rns_fused_matmul`.

    Same contract, same bits: routes ONE launch to the channel- or
    column-sharded shard_map region over ``ctx.mesh``'s ``ctx.axis``, picked
    by the `comms` bytes-on-wire model under ``layout="auto"``.  A forced
    layout is a PREFERENCE, resolved per launch: a launch whose C (or N)
    the mesh axis does not divide falls back to the other layout when
    feasible, else to the plain replicated launch — an Engine-level
    ``dist_layout="channel"`` must serve configs whose bases mix channel
    counts (e.g. the C=5 down-proj basis next to C=4 attention bases).
    ``ctx`` defaults to the ambient `repro.dist.context.current()`; with no
    context (or a 1-shard mesh) this IS `rns_fused_matmul`.
    """
    ctx = ctx if ctx is not None else current()
    plain = functools.partial(rns_fused_matmul, x, w, basis,
                              quantize=quantize, gate=gate, emit=emit,
                              scale_row=scale_row, scale_col=scale_col,
                              scale=scale, interpret=interpret)
    if ctx is None or ctx.nshards <= 1:
        return plain()

    if isinstance(w, RNSTensor):
        basis = w.basis
    elif isinstance(x, RNSTensor):
        basis = x.basis
    elif basis is None:
        from repro.core.rns import basis_for_int8_matmul
        basis = basis_for_int8_matmul(np.shape(x)[-1])
    M = (x.shape if isinstance(x, RNSTensor) else np.shape(x))[-2]
    N = (w.shape if isinstance(w, RNSTensor) else np.shape(w))[-1]

    lay = resolve_layout(layout or ctx.layout, ctx.nshards, basis, M, N,
                         emit=emit)
    if lay == "replicate":
        return plain()

    # operand lowering, mirroring rns_fused_matmul (one rule, same bits):
    # scale_row/scale_col reshape to (M, 1)/(1, N); a generic scale lowers
    # to the cheapest of row/col/full by its broadcast shape.
    if isinstance(x, RNSTensor) and scale_row is None:
        scale_row = x.scale
    srow = (jnp.asarray(scale_row, jnp.float32).reshape(M, 1)
            if scale_row is not None else None)
    scol = (jnp.asarray(scale_col, jnp.float32).reshape(1, N)
            if scale_col is not None else None)
    sc = None
    if scale is not None:
        s = jnp.asarray(scale, jnp.float32)
        bshape = jnp.broadcast_shapes(s.shape, (M, N))
        if bshape != (M, N):
            raise ValueError(f"scale {s.shape} does not broadcast "
                             f"against the ({M}, {N}) output")
        s2 = s.reshape((1,) * (2 - s.ndim) + s.shape) if s.ndim < 2 else s
        if s2.shape[0] == 1:
            scol = jnp.broadcast_to(s2, (1, N))
        elif s2.shape[1] == 1:
            srow = jnp.broadcast_to(s2, (M, 1))
        else:
            sc = jnp.broadcast_to(s2, (M, N))

    if lay == "channel":
        if emit == "residues":
            # replicated emit: zero comms — re-encoding residues per channel
            # needs every device's moduli, and the replicated (C, M, N)
            # output is exactly what the next channel-sharded launch's
            # in_specs slice (DESIGN.md §17)
            return plain()
        return _channel_call(ctx, x, w, basis, quantize=quantize, gate=gate,
                             srow=srow, scol=scol, sc=sc,
                             interpret=interpret)
    return _column_call(ctx, x, w, basis, quantize=quantize, gate=gate,
                        emit=emit, srow=srow, scol=scol, sc=sc,
                        interpret=interpret)
