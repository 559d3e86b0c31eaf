"""Pure-jnp oracles for every Pallas kernel in this package.

Each `*_ref` function is numerically *exact* (integer kernels) or
allclose-equivalent (attention) to its kernel twin; the test suite sweeps
shapes/dtypes and asserts agreement.  The integer oracles consume the same
`repro.core.channel_plan.ChannelPlan` (schedules AND ladder code) as the
kernels, so kernel and oracle provably apply the same congruence ladder.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel_plan import ChannelPlan
from repro.core.conversion_plan import ConversionPlan
from repro.core.conversion_plan import forward as _forward_convert

__all__ = [
    "channel_schedules",
    "rns_matmul_ref",
    "rns_fused_matmul_ref",
    "rns_fused_chain_ref",
    "rns_emit_ref",
    "rns_modmul_ref",
    "rns_forward_ref",
    "rns_reverse_ref",
    "fold_ref",
    "attention_ref",
]


def channel_schedules(moduli: Tuple[int, ...], bound: int,
                      max_rungs: int = 6) -> Tuple[np.ndarray, np.ndarray, int]:
    """Per-channel fold ladders, padded to a common rung count.

    Compatibility view over :class:`~repro.core.channel_plan.ChannelPlan`
    (the single owner of all Stage-④ precomputation).  Returns
    (sched, mods, n_sub):
      sched: (C, R, 2) int32 — (shift, constant) rungs, no-op padded.
      mods:  (C,) int32 moduli.
      n_sub: conditional-subtract count covering every channel.
    """
    plan = ChannelPlan.build(moduli, bound, max_rungs=max_rungs)
    return plan.sched, plan.mods, plan.n_sub


def rns_matmul_ref(a_res, b_res, moduli: Sequence[int]):
    """Oracle for the RNS channel matmul.

    a_res: (C, M, K) int8/int32 residues in [0, m_c)
    b_res: (C, K, N) idem
    returns (C, M, N) int32 canonical residues of the per-channel products.

    The contraction accumulates *unreduced* in int32 (the carry-save analogue)
    and folds once at the end — the paper's deferred-reduction organization.
    The fold is `ChannelPlan.apply_ladder`, the same code the kernels run.
    """
    plan = ChannelPlan.for_matmul(tuple(int(m) for m in moduli),
                                  a_res.shape[-1])
    acc = jnp.einsum("cmk,ckn->cmn", a_res.astype(jnp.int32),
                     b_res.astype(jnp.int32))
    return jnp.stack([plan.apply_ladder(acc[c], c)
                      for c in range(plan.k)], axis=0)


def rns_fused_matmul_ref(xq, wq, basis, *, scale=None):
    """Oracle for the Stage ②–⑤ megakernel (`rns_fused.rns_fused_matmul`,
    int8-activation form): the staged broadcast-datapath pipeline through
    the jnp backends — the same ChannelPlan fold and ConversionPlan reverse
    the megakernel replays in its epilogue, so agreement is bit-exact.
    """
    from repro.core import channel_plan as cp
    from repro.core.rns_tensor import RNSTensor

    if isinstance(wq, RNSTensor):
        res = cp.matmul_broadcast(xq, wq.residues, basis.moduli,
                                  encoded=True, backend="jnp")
    else:
        res = cp.matmul_broadcast(xq, wq, basis.moduli, backend="jnp")
    return ConversionPlan.for_basis(basis).reverse(res, backend="jnp",
                                                   scale=scale)


def rns_fused_chain_ref(x, w_gate, w_up, w_down, basis, *, act=jax.nn.silu):
    """Oracle for a residue-resident GLU-MLP chain (DESIGN.md §14): the
    UNCHAINED per-linear staged composition under the shared requantize rule.

    Every linear runs as standalone jnp ops — quantize, forward conversion,
    canonical channel matmul, MRC reverse — and the up-projection exit
    applies exactly the `quant.requant_const` round/clip the chained
    kernel's ``emit="residues"`` epilogue applies, so the chained path
    (one activation forward conversion, one MRC exit) must agree bit-for-bit
    (`tests/test_chain.py`).  ``x`` is the float (M, K) block entering the
    MLP; weights are raw float (K, F)/(K, F)/(F, N) or RNSTensors already in
    ``basis`` (the chain basis — `rns.basis_for_chain(F)`).
    """
    from repro.core import channel_plan as cp
    from repro.core.quant import QMAX, quantize_int8, requant_const
    from repro.core.rns_tensor import RNSTensor, encode

    moduli = tuple(int(m) for m in basis.moduli)
    conv = ConversionPlan.for_basis(basis)

    def enc(w):
        return w if isinstance(w, RNSTensor) else encode(w, basis)

    wg, wu, wd = enc(w_gate), enc(w_up), enc(w_down)
    K, F = x.shape[-1], wu.shape[-1]
    plan_k = ChannelPlan.for_matmul(moduli, K, signed=False)
    plan_f = ChannelPlan.for_matmul(moduli, F, signed=False)

    # chain entry: the one activation quantize + forward conversion
    xq, sx = quantize_int8(x, axis=-1)
    x_res = _forward_convert(xq, moduli, backend="jnp",
                             dtype=plan_k.residue_dtype)

    def matmul(a_res, wt, plan):
        res = cp.matmul(a_res, wt.residues.astype(plan.residue_dtype),
                        moduli, backend="jnp", plan=plan)
        return conv.reverse(res, backend="jnp")

    # gate branch: float exit (its own domain boundary), activation, requant
    y_gate = (matmul(x_res, wg, plan_k) * sx) * wg.scale
    gq, sg = quantize_int8(act(y_gate), axis=-1)

    # up-projection exit: the shared in-domain requantize rule
    creq = requant_const(wu.scale, K)
    t = matmul(x_res, wu, plan_k) * wu.scale
    q_up = jnp.clip(jnp.round(t / creq), -QMAX, QMAX)
    s_up = sx * creq

    # down-projection: gated canonical product, MRC exit, pinned scale order
    u_res = _forward_convert(q_up.astype(jnp.int32), moduli, backend="jnp",
                             dtype=plan_f.residue_dtype)
    g_res = _forward_convert(gq, moduli, backend="jnp",
                             dtype=plan_f.residue_dtype)
    a_res = cp.modmul(u_res, g_res, moduli,
                      backend="jnp").astype(plan_f.residue_dtype)
    return (matmul(a_res, wd, plan_f) * (s_up * sg)) * wd.scale


def rns_emit_ref(x_res, w_res, basis, scale_row, scale_col):
    """Oracle for the megakernel's ``emit="residues"`` epilogue (DESIGN.md
    §14): canonical (C, M, K) × (C, K, N) residues → the requantized
    product's (C, M, N) residues and its (M, 1) scale.

    Staged jnp ops in the kernel's order — channel matmul + fold, MRC
    reverse, ``clip(round(y·s_col / c), ±QMAX)`` with c = `quant.
    requant_const(s_col, K)`, forward conversion; the scale is
    ``s_row · c``."""
    from repro.core.quant import QMAX, requant_const

    moduli = tuple(int(m) for m in basis.moduli)
    K, N = w_res.shape[-2], w_res.shape[-1]
    res = rns_matmul_ref(x_res, w_res, moduli)
    val = ConversionPlan.for_basis(basis).reverse(res, backend="jnp")
    scol = jnp.asarray(scale_col, jnp.float32).reshape(1, N)
    creq = requant_const(scol, K)
    q = jnp.clip(jnp.round((val * scol) / creq), -QMAX, QMAX)
    out = _forward_convert(q.astype(jnp.int32), moduli, backend="jnp",
                           dtype=jnp.asarray(x_res).dtype)
    srow = jnp.asarray(scale_row, jnp.float32).reshape(-1, 1)
    return out, srow * creq


def rns_modmul_ref(a_res, b_res, moduli: Sequence[int]):
    """Oracle for the elementwise residue multiply: (C, ...) → (C, ...)."""
    plan = ChannelPlan.for_product(tuple(int(m) for m in moduli))
    p = a_res.astype(jnp.int32) * b_res.astype(jnp.int32)
    return jnp.stack([plan.apply_ladder(p[c], c)
                      for c in range(plan.k)], axis=0)


def rns_forward_ref(x, moduli: Sequence[int]):
    """Oracle for the forward-conversion kernel: (…,) int → (C, …) int32.

    Delegates to the jnp twin in `conversion_plan` — the ONE forward
    converter (DESIGN.md §10) — pinned to int32 like the kernel output.
    """
    import jax.numpy as jnp

    return _forward_convert(x, tuple(int(m) for m in moduli), backend="jnp",
                            dtype=jnp.int32)


def rns_reverse_ref(residues, moduli: Sequence[int], scale=None):
    """Oracle for the fused MRC reverse kernel: (C, …) residues → (…) f32.

    Delegates to `ConversionPlan`'s jnp twin; the kernel replays the same
    integer digit schedule and float32 limb recombination, so agreement is
    bit-exact.
    """
    return ConversionPlan.build(tuple(int(m) for m in moduli)).reverse(
        residues, backend="jnp", scale=scale)


def fold_ref(x, moduli: Sequence[int], bound: int):
    """Oracle for the standalone fold kernel: (C, ...) int32 → canonical."""
    plan = ChannelPlan.build(tuple(int(m) for m in moduli), int(bound))
    return jnp.stack([plan.apply_ladder(x[c].astype(jnp.int32), c)
                      for c in range(plan.k)], axis=0)


def attention_ref(q, k, v, *, causal: bool = True, window: int | None = None,
                  softcap: float | None = None, scale: float | None = None,
                  pad=None, qpos=None, kpos=None):
    """Oracle attention: (B, H, Sq, D), (B, H, Sk, D), (B, H, Sk, D).

    Causal + optional sliding window + optional logit softcap — the exact
    masking semantics the models use (gemma2/h2o-danube/hymba variants).
    ``pad`` ((B,) int32, optional) marks the first pad[b] key positions of
    sequence b invalid (the ragged left-padded batch mask); fully-masked
    query rows produce zeros, matching the kernel.

    ``qpos``/``kpos`` ((Sq,)/(Sk,) or (B, Sq)/(B, Sk) int32, optional)
    switch to EXPLICIT absolute positions — the paged-KV gather layout
    (DESIGN.md §15), where a key row's position is given by the block table
    rather than its buffer index and −1 marks an invalid (unmapped / pad)
    row.  Mutually exclusive with ``pad``; causal/window masking then
    compares the explicit coordinates.
    """
    sq, sk = q.shape[-2], k.shape[-2]
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    if qpos is not None or kpos is not None:
        if pad is not None:
            raise ValueError("pad= and explicit qpos/kpos= are mutually "
                             "exclusive")
        qp = jnp.arange(sq, dtype=jnp.int32) + (sk - sq) if qpos is None \
            else jnp.asarray(qpos, jnp.int32)
        kp = jnp.arange(sk, dtype=jnp.int32) if kpos is None \
            else jnp.asarray(kpos, jnp.int32)
        qp = qp[None] if qp.ndim == 1 else qp                # (Bm, sq)
        kp = kp[None] if kp.ndim == 1 else kp                # (Bm, sk)
        mask = (kp[:, None, :] >= 0) & (qp[:, :, None] >= 0)
        if causal:
            mask &= kp[:, None, :] <= qp[:, :, None]
        if window is not None:
            mask &= kp[:, None, :] > qp[:, :, None] - window
    else:
        qpos_i = jnp.arange(sq)[:, None] + (sk - sq)
        kpos_i = jnp.arange(sk)[None, :]
        mask = jnp.ones((sq, sk), dtype=bool)
        if causal:
            mask &= kpos_i <= qpos_i
        if window is not None:
            mask &= kpos_i > qpos_i - window
        mask = mask[None]                                    # (1, sq, sk)
        if pad is not None:
            mask = mask & (kpos_i[None] >= jnp.asarray(pad)[:, None, None])
    logits = jnp.where(mask[:, None], logits, -1e30)
    alive = mask.any(axis=-1)[:, None, :, None]              # (B|1,1,sq,1)
    p = jnp.where(alive, jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
