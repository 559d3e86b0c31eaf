"""Scan-over-layers decoder stack covering all ten assigned architectures.

One block body, parameterized by ModelConfig, compiled once by XLA thanks to
lax.scan over stacked per-layer parameters (compile time stays flat in depth
— essential for the 512-device dry-runs).  Per-layer structural variation is
data, not code:

  * attention windows   — (n_blocks, layers_per_block) int32 scanned array
                          (gemma2 local/global alternation, hymba's three
                          global layers, danube's uniform SWA, full = seq);
  * MoE/dense interleave— static `block_structure` (llama4 scans over pairs);
  * mixers              — attention ("attn"), Mamba2 SSD ("ssm"), or both in
                          parallel ("hybrid", hymba-style fused heads).

Three entry points per model, matching the dry-run cells:
  forward()      — full-sequence logits (train / prefill_32k lowering)
  prefill()      — forward + KV/SSM cache construction
  decode_step()  — single-token step with caches (decode_32k / long_500k);
                   SWA layers use O(window) ring buffers, SSM layers O(1)
                   state — the sub-quadratic-memory requirement of long_500k.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from .layers import (apply_rope, attention, dtype_of, linear, linear_qkv,
                     make_dense_params, mlp_chain, rms_norm, rope, sinusoidal,
                     update_cache_full, update_cache_ring)
from .moe import make_moe_params, moe_apply
from .ssm import init_ssm_cache, make_ssm_params, ssm_apply, ssm_decode_step

__all__ = ["make_params", "forward", "prefill", "decode_step", "init_cache",
           "window_array", "count_params", "active_params"]

FULL_WINDOW = 1 << 30


# ------------------------------------------------------------------ params --
def _make_attn_params(key, cfg: ModelConfig, dtype):
    d, H, Hk, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": make_dense_params(ks[0], d, H * dh, dtype),
        "wk": make_dense_params(ks[1], d, Hk * dh, dtype),
        "wv": make_dense_params(ks[2], d, Hk * dh, dtype),
        "wo": make_dense_params(ks[3], H * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), dtype)
        p["k_norm"] = jnp.zeros((dh,), dtype)
    return p


def _make_mlp_params(key, cfg: ModelConfig, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": make_dense_params(ks[0], d, f, dtype),
        "w_up": make_dense_params(ks[1], d, f, dtype),
        "w_down": make_dense_params(ks[2], f, d, dtype),
    }


def _make_layer_params(key, cfg: ModelConfig, layer: int, dtype):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm_mix": jnp.zeros((d,), dtype),
                         "norm_mlp": jnp.zeros((d,), dtype)}
    if cfg.post_norm:
        p["norm_mix_post"] = jnp.zeros((d,), dtype)
        p["norm_mlp_post"] = jnp.zeros((d,), dtype)
    kind = _mixer_kind(cfg)
    if kind in ("attn", "hybrid"):
        p["attn"] = _make_attn_params(ks[0], cfg, dtype)
    if kind in ("ssm", "hybrid"):
        p["ssm"] = make_ssm_params(ks[1], cfg, dtype)
    if kind == "hybrid":
        p["norm_attn_out"] = jnp.zeros((d,), dtype)
        p["norm_ssm_out"] = jnp.zeros((d,), dtype)
    if cfg.mlp_kind(layer) == "moe":
        p["moe"] = make_moe_params(ks[2], cfg, dtype)
    elif cfg.d_ff > 0:
        p["mlp"] = _make_mlp_params(ks[3], cfg, dtype)
    else:
        del p["norm_mlp"]          # attention-free mamba2: mixer-only blocks
        if cfg.post_norm:
            del p["norm_mlp_post"]
    return p


def _mixer_kind(cfg: ModelConfig) -> str:
    if cfg.hybrid:
        return "hybrid"
    if cfg.ssm and cfg.attention == "none":
        return "ssm"
    return "attn"


def make_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = dtype_of(cfg)
    keys = jax.random.split(key, cfg.n_blocks + 3)
    # one block = layers_per_block consecutive layers (llama4: dense+moe pair)
    blocks = []
    for b in range(cfg.n_blocks):
        sub = {}
        for i in range(cfg.layers_per_block):
            layer = b * cfg.layers_per_block + i
            sub[f"sub{i}"] = _make_layer_params(
                jax.random.fold_in(keys[b], i), cfg, layer, dtype)
        blocks.append(sub)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *blocks)
    params = {
        "embed": (jax.random.normal(keys[-3], (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype),
        "blocks": stacked,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = make_dense_params(keys[-2], cfg.d_model,
                                              cfg.vocab_size, dtype)
    return params


def window_array(cfg: ModelConfig, seq_len: int) -> np.ndarray:
    """(n_blocks, layers_per_block) int32 effective windows."""
    out = np.zeros((cfg.n_blocks, cfg.layers_per_block), np.int32)
    for b in range(cfg.n_blocks):
        for i in range(cfg.layers_per_block):
            w = cfg.window_for_layer(b * cfg.layers_per_block + i, seq_len)
            out[b, i] = min(w, FULL_WINDOW)
    return out


# ----------------------------------------------------------------- sublayers
def _attn_full(p, h, cfg: ModelConfig, window, positions):
    """Full-sequence attention sublayer (train/prefill).  Returns out, (k,v)."""
    B, S, d = h.shape
    H, Hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
    spec = cfg.linear_spec
    if spec.is_rns and spec.domain == "residue":
        # stacked-QKV chain (DESIGN.md §14): one residue-domain launch for
        # the three shared-operand projections — one activation forward
        # conversion instead of three, bit-identical outputs.
        q, k, v = linear_qkv(x, (p["attn"]["wq"], p["attn"]["wk"],
                                 p["attn"]["wv"]), spec)
    else:
        q = linear(x, p["attn"]["wq"], spec)
        k = linear(x, p["attn"]["wk"], spec)
        v = linear(x, p["attn"]["wv"], spec)
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, Hk, dh)
    v = v.reshape(B, S, Hk, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["attn"]["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["attn"]["k_norm"], cfg.norm_eps)
    if cfg.pos == "rope":
        cos, sin = rope(positions, dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    o = attention(q, k, v, positions, positions, window=window,
                  softcap=cfg.softcap_attn, block_kv=cfg.attn_block_kv)
    o = linear(o.reshape(B, S, H * dh), p["attn"]["wo"], cfg.linear_spec)
    o = checkpoint_name(o, "mixer_out")
    if cfg.post_norm:
        o = rms_norm(o, p["norm_mix_post"], cfg.norm_eps)
    return o, (k, v)


def _paged_rows(block_table, positions, shift, block: int):
    """Pool rows ``phys·block + offset`` of each slot's attended view (0,
    a trash row, where unmapped) and their key positions (−1 ⇒ invalid):
    `paged_view`'s index rule."""
    nlog = block_table.shape[1]
    lp = (jnp.arange(nlog * block, dtype=jnp.int32)[None]
          - shift[:, None])                                   # (B, S)
    phys = jnp.take_along_axis(block_table,
                               jnp.clip(lp // block, 0, nlog - 1), axis=1)
    mapped = (lp >= 0) & (phys >= 0)
    rows = jnp.where(mapped, phys * block + lp % block, 0)
    kpos = jnp.where(mapped & (lp <= positions[:, None]), lp, -1)
    return rows, kpos


def paged_view(pool_k, pool_v, block_table, positions, shift):
    """Each slot's attended view of a paged K/V pool.

    ``pool_*``: (n_phys, block, Hk, dh); ``block_table``: (B, n_logical)
    logical → physical block (−1 ⇒ unmapped); ``positions``: (B,) last
    written logical position; ``shift``: (B,) view offset.  Returns
    ``(k, v, kpos)`` with k/v (B, n_logical·block, Hk, dh): view slot s
    holds logical position s − shift, and ``kpos`` is that position, or −1
    where it is unmapped, negative or not yet written.  Invalid rows gather
    trash; their masked scores contribute exact float zeros (DESIGN.md §11).

    The shift places every key in the slot the static engine's left-padded
    cache uses: an accelerator's reductions over the key axis (softmax sum,
    p·V) group by slot, so the same keys at other slots round differently.
    """
    rows, kpos = _paged_rows(block_table, positions, shift, pool_k.shape[1])
    k = pool_k.reshape(-1, *pool_k.shape[2:])[rows]
    v = pool_v.reshape(-1, *pool_v.shape[2:])[rows]
    return k, v, kpos


def _kv_rows(k, v):
    """The paged pool's rows: each token's K then its V, ``(…, Hk, dh)`` →
    ``(…, 2·Hk·dh)`` (`serve.paged_cache.init_paged_cache`)."""
    return jnp.concatenate([k.reshape(*k.shape[:-2], -1),
                            v.reshape(*v.shape[:-2], -1)], -1)


class _PagedStep(NamedTuple):
    """One paged decode step's indices, shared by every layer (see
    `_paged_step`)."""
    rows: jax.Array      # (B, S) pool row of each view slot
    kpos: jax.Array      # (B, S) key position, −1 invalid
    own: jax.Array       # (B, S) the view slot that takes the step's K/V
    write: jax.Array     # (B,) pool row the step's K/V go to


def _paged_step(block_table, pos, positions, block: int) -> _PagedStep:
    """The indices of a paged decode step: `paged_view`'s rows and key
    positions, and where each slot's new K/V go in the view and the pool."""
    B, nlog = block_table.shape
    posv = jnp.broadcast_to(positions if positions is not None else pos,
                            (B,))
    pos = jnp.broadcast_to(pos, (B,))
    rows, kpos = _paged_rows(block_table, posv, pos - posv, block)
    # idle/retired slots (no mapped block) and positions past the mapped
    # range write to the reserved trash block 0, which is never read
    # unmasked — a frozen `done` slot can keep "writing" harmlessly.
    blk = posv // block
    phys = jnp.where(blk < nlog,
                     block_table[jnp.arange(B), jnp.minimum(blk, nlog - 1)],
                     -1)
    # a mapped write lands at view slot posv + shift = pos
    own = ((jnp.arange(nlog * block, dtype=jnp.int32)[None] == pos[:, None])
           & (phys >= 0)[:, None])
    return _PagedStep(rows, kpos, own,
                      jnp.maximum(phys, 0) * block + posv % block)


def _attn_decode(p, h, cfg: ModelConfig, window, pos, cache, positions=None,
                 paged=None):
    """One-token attention with cache update.  h: (B, 1, d).

    ``pos`` is the scalar cache-slot index (padded coordinate: slot s holds
    the token at padded index s); ``positions`` (optional, (B,)) are the
    per-sequence *real* positions ``pos − pad[i]`` for ragged left-padded
    batches — they drive RoPE and the attention mask, so a short prompt's
    RoPE phases and window are not shifted by its batchmates' padding.

    ``paged`` (``(_PagedStep, layer)``, optional) switches the layer to
    the PAGED cache layout (serve/paged_cache.py, DESIGN.md §15): ``cache``
    then holds the stacked physical pool ``{"kv": (L, n_phys·block,
    2·Hk·dh)}`` shared by all slots, one token's K then V a row, read-only
    here: the layer gathers its view from layer ``layer`` of it, puts its
    own new K/V at the view slot the step writes, and returns only that
    row ``{"kv": (B, 2·Hk·dh)}`` — `decode_step` scatters every layer's
    rows into the pool after the layer scan.  ``positions`` is the
    per-slot (B,) logical write position and ``pos`` the (B,) slot of the
    attended view it lands in: the view holds logical position r at slot
    ``r + pos − positions``, so a slot can reproduce the static engine's
    left-padded cache layout key for key.
    """
    B = h.shape[0]
    H, Hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
    spec = cfg.linear_spec
    if spec.is_rns and spec.domain == "residue":
        q, k, v = linear_qkv(x, (p["attn"]["wq"], p["attn"]["wk"],
                                 p["attn"]["wv"]), spec)
    else:
        q = linear(x, p["attn"]["wq"], spec)
        k = linear(x, p["attn"]["wk"], spec)
        v = linear(x, p["attn"]["wv"], spec)
    q = q.reshape(B, 1, H, dh)
    k = k.reshape(B, 1, Hk, dh)
    v = v.reshape(B, 1, Hk, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["attn"]["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["attn"]["k_norm"], cfg.norm_eps)
    qpos = pos[None] if positions is None else positions[:, None]
    if cfg.pos == "rope":
        cos, sin = rope(qpos, dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if paged is not None:
        if "pos" in cache:
            raise ValueError(
                "paged decode does not support ring (SWA) caches: the ring's "
                "cache_pos is one (W,) vector shared across the batch, so "
                "per-slot write positions have nowhere to live — serve "
                "SWA/hybrid-SWA architectures through the static engine")
        step, layer = paged
        pool = cache["kv"]                  # (L, n_phys·block, 2·Hk·dh)
        new = _kv_rows(k, v).astype(pool.dtype)            # (B, 1, 2·Hk·dh)
        # the view the write-then-gather order reads, key for key, with no
        # copy of the pool: the gather reads the step's incoming pool and
        # the slot's own row comes from this step's K/V
        kv = jnp.where(step.own[..., None], new, pool[layer, step.rows])
        gk = kv[..., :Hk * dh].reshape(B, -1, Hk, dh)
        gv = kv[..., Hk * dh:].reshape(B, -1, Hk, dh)
        o = attention(q, gk.astype(q.dtype), gv.astype(q.dtype), qpos,
                      step.kpos, window=window, softcap=cfg.softcap_attn,
                      block_kv=cfg.attn_block_kv)
        o = linear(o.reshape(B, 1, H * dh), p["attn"]["wo"], cfg.linear_spec)
        if cfg.post_norm:
            o = rms_norm(o, p["norm_mix_post"], cfg.norm_eps)
        return o, {"kv": new[:, 0]}
    if jnp.ndim(pos):
        raise ValueError("per-slot (B,) decode positions need block_table "
                         "paging; the contiguous cache layout shares one "
                         "scalar write position")
    if "pos" in cache:                     # ring buffer (SWA layer)
        ck, cv, cp = update_cache_ring(cache["k"], cache["v"], cache["pos"],
                                       k, v, pos)
        new_cache = {"k": ck, "v": cv, "pos": cp}
        kpad = cp                          # (w,) padded indices, −1 unwritten
    else:                                  # full cache (global layer)
        ck, cv = update_cache_full(cache["k"], cache["v"], k, v, pos)
        new_cache = {"k": ck, "v": cv}
        kpad = jnp.arange(ck.shape[1], dtype=jnp.int32)
    if positions is None:
        kpos = kpad
    else:
        # shift the slot-aligned padded indices into per-sequence real
        # positions; pad slots (real position < 0) and unwritten ring slots
        # (padded index −1) become −1 ⇒ invalid keys.
        pad = pos - positions                                  # (B,)
        kpos = kpad[None] - pad[:, None]
        kpos = jnp.where((kpad[None] >= 0) & (kpos >= 0), kpos, -1)
    o = attention(q, ck.astype(q.dtype), cv.astype(q.dtype), qpos, kpos,
                  window=window, softcap=cfg.softcap_attn,
                  block_kv=cfg.attn_block_kv)
    o = linear(o.reshape(B, 1, H * dh), p["attn"]["wo"], cfg.linear_spec)
    if cfg.post_norm:
        o = rms_norm(o, p["norm_mix_post"], cfg.norm_eps)
    return o, new_cache


def _ssm_full(p, h, cfg: ModelConfig, valid=None):
    x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
    o = ssm_apply(p["ssm"], x, cfg, valid=valid)
    if cfg.post_norm:
        o = rms_norm(o, p["norm_mix_post"], cfg.norm_eps)
    return o


def _act(name: str):
    return jax.nn.silu if name == "silu" else jax.nn.gelu


def _mlp(p, h, cfg: ModelConfig):
    x = rms_norm(h, p["norm_mlp"], cfg.norm_eps)
    spec = cfg.linear_spec
    if spec.is_rns and spec.domain == "residue" and cfg.glu:
        # residue-resident GLU chain (DESIGN.md §14): up → in-domain gate →
        # down without leaving the RNS domain; one activation forward
        # conversion + one MRC exit for the whole chain.
        o = mlp_chain(x, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                      p["mlp"]["w_down"], spec, _act(cfg.act))
    else:
        g = _act(cfg.act)(linear(x, p["mlp"]["w_gate"], spec))
        if cfg.glu:
            g = g * linear(x, p["mlp"]["w_up"], spec)
        o = linear(g, p["mlp"]["w_down"], spec)
    o = checkpoint_name(o, "mlp_out")
    if cfg.post_norm:
        o = rms_norm(o, p["norm_mlp_post"], cfg.norm_eps)
    return o


def _moe(p, h, cfg: ModelConfig):
    x = rms_norm(h, p["norm_mlp"], cfg.norm_eps)
    o, aux = moe_apply(p["moe"], x, cfg)
    if cfg.post_norm:
        o = rms_norm(o, p["norm_mlp_post"], cfg.norm_eps)
    return o, aux


# ------------------------------------------------------------------- layers -
def _layer_full(p, h, cfg: ModelConfig, layer_in_block: int, window,
                positions):
    kind = _mixer_kind(cfg)
    aux = jnp.float32(0.0)
    valid = _valid_of(positions)
    if kind == "attn":
        o, _ = _attn_full(p, h, cfg, window, positions)
        h = h + o
    elif kind == "ssm":
        h = h + _ssm_full(p, h, cfg, valid)
    else:  # hybrid: parallel attention + ssm on the same normed input
        oa, _ = _attn_full(p, h, cfg, window, positions)
        os_ = _ssm_full(p, h, cfg, valid)
        oa = rms_norm(oa, p["norm_attn_out"], cfg.norm_eps)
        os_ = rms_norm(os_, p["norm_ssm_out"], cfg.norm_eps)
        h = h + 0.5 * (oa + os_)
    if cfg.mlp_kind(layer_in_block) == "moe":
        o, aux = _moe(p, h, cfg)
        h = h + o
    elif cfg.d_ff > 0:
        h = h + _mlp(p, h, cfg)
    return h, aux


def _stack_apply(cfg: ModelConfig, params, h, windows, positions,
                 want_cache: bool):
    """Scan over blocks (train / full-sequence forward)."""

    def body(carry, xs):
        hh = carry
        blk, wrow = xs
        auxes = jnp.float32(0.0)
        for i in range(cfg.layers_per_block):
            hh, aux = _layer_full(blk[f"sub{i}"], hh, cfg, i, wrow[i],
                                  positions)
            auxes = auxes + aux
        return hh, auxes

    if not cfg.scan_layers:          # unrolled: exact HLO cost accounting
        auxes = jnp.float32(0.0)
        for b in range(cfg.n_blocks):
            blk = jax.tree.map(lambda x: x[b], params["blocks"])
            h, aux = body(h, (blk, windows[b]))
            auxes = auxes + aux
        return h, auxes / cfg.n_blocks, None

    if cfg.remat and cfg.remat_policy != "none":
        if cfg.remat_policy == "save_ar":
            # keep the row-parallel projection outputs (the tensors whose
            # recompute would repeat the TP all-reduces) — backward reuses
            # them, cutting the per-layer collective multiplier 3× → 2×
            # (EXPERIMENTS.md §Perf cell B).
            policy = jax.checkpoint_policies.save_only_these_names(
                "mixer_out", "mlp_out")
            body = jax.checkpoint(body, policy=policy)
        else:
            body = jax.checkpoint(body)
    h, auxes = jax.lax.scan(body, h, (params["blocks"], windows))
    return h, jnp.mean(auxes), None


# ------------------------------------------------------------------ forward -
def _embed(cfg: ModelConfig, params, batch):
    """Token/embedding frontend + positions.

    ``batch["pad"]`` (optional, (B,) int32 left-pad counts) makes positions
    per-sequence: ``positions[i] = arange(S) − pad[i]`` — negative at padded
    slots, which downstream attention treats as invalid keys (DESIGN.md §11).
    Without it positions stay the shared (S,) arange (training path,
    bit-identical to before).
    """
    if cfg.frontend == "embeddings":
        h = batch["embeds"].astype(dtype_of(cfg))
        B, S = h.shape[0], h.shape[1]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        h = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.arange(S, dtype=jnp.int32)
    pad = batch.get("pad")
    if pad is not None:
        positions = positions[None] - pad[:, None].astype(jnp.int32)
    if cfg.pos == "sinusoidal":
        pe = sinusoidal(positions, cfg.d_model)
        h = h + (pe[None] if positions.ndim == 1 else pe).astype(h.dtype)
    return h, positions


def _valid_of(positions):
    """(B, S) bool validity mask from per-sequence positions, or None."""
    return (positions >= 0) if positions.ndim == 2 else None


def _lm_head(cfg: ModelConfig, params, h):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, w).astype(jnp.float32)
    if cfg.softcap_final is not None:
        logits = jnp.tanh(logits / cfg.softcap_final) * cfg.softcap_final
    return logits


def forward(cfg: ModelConfig, params, batch):
    """Full-sequence logits: (B, S, vocab) float32."""
    h, positions = _embed(cfg, params, batch)
    windows = jnp.asarray(window_array(cfg, h.shape[1]))
    h, aux, _ = _stack_apply(cfg, params, h, windows, positions, False)
    return _lm_head(cfg, params, h), aux


# ------------------------------------------------------------------- caches -
def _layer_cache_spec(cfg: ModelConfig, layer: int, batch: int, smax: int,
                      dtype):
    """Zeroed decode cache for one layer."""
    kind = _mixer_kind(cfg)
    out: Dict[str, Any] = {}
    Hk, dh = cfg.num_kv_heads, cfg.head_dim
    if kind in ("attn", "hybrid"):
        w = cfg.window_for_layer(layer, smax)
        if w < smax:          # bounded ring buffer (SWA layer)
            out["k"] = jnp.zeros((batch, w, Hk, dh), dtype)
            out["v"] = jnp.zeros((batch, w, Hk, dh), dtype)
            out["pos"] = jnp.full((w,), -1, jnp.int32)
        else:
            out["k"] = jnp.zeros((batch, smax, Hk, dh), dtype)
            out["v"] = jnp.zeros((batch, smax, Hk, dh), dtype)
    if kind in ("ssm", "hybrid"):
        out["ssm"] = init_ssm_cache(cfg, batch, dtype)
    return out


def init_cache(cfg: ModelConfig, batch: int, smax: int):
    """Stacked decode caches for the whole stack.

    Layers inside a block can have different window sizes (gemma2 pairs),
    so caches are keyed per sub-layer and stacked over blocks only when the
    shapes agree; otherwise kept per-sub (static structure either way).
    """
    dtype = dtype_of(cfg)
    out = {}
    for i in range(cfg.layers_per_block):
        per_block = [
            _layer_cache_spec(cfg, b * cfg.layers_per_block + i, batch, smax,
                              dtype)
            for b in range(cfg.n_blocks)
        ]
        shapes = [jax.tree.map(lambda x: x.shape, pb) for pb in per_block]
        if all(s == shapes[0] for s in shapes):
            out[f"sub{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs, 0),
                                          *per_block)
        else:
            # heterogeneous windows within the column (hymba's 3 global
            # layers): keep a per-block list pytree (no scan over caches).
            out[f"sub{i}"] = {"per_block": per_block}
    return out


def _cache_is_stacked(cache_col) -> bool:
    return "per_block" not in cache_col


# -------------------------------------------------------------- decode step -
def _layer_decode(p, h, cfg: ModelConfig, block_layer, window, pos, cache,
                  positions=None, paged=None):
    kind = _mixer_kind(cfg)
    new_cache = {}
    if kind == "attn":
        o, nc = _attn_decode(p, h, cfg, window, pos, cache, positions, paged)
        new_cache.update(nc)
        h = h + o
    elif kind == "ssm":
        x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
        o, ns = ssm_decode_step(p["ssm"], x, cache["ssm"], cfg)
        if cfg.post_norm:
            o = rms_norm(o, p["norm_mix_post"], cfg.norm_eps)
        new_cache["ssm"] = ns
        h = h + o
    else:
        oa, nc = _attn_decode(p, h, cfg, window, pos,
                              {k: v for k, v in cache.items() if k != "ssm"},
                              positions, paged)
        x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
        os_, ns = ssm_decode_step(p["ssm"], x, cache["ssm"], cfg)
        new_cache.update(nc)
        new_cache["ssm"] = ns
        oa = rms_norm(oa, p["norm_attn_out"], cfg.norm_eps)
        os_ = rms_norm(os_, p["norm_ssm_out"], cfg.norm_eps)
        h = h + 0.5 * (oa + os_)
    if cfg.mlp_kind(block_layer) == "moe":
        o, _ = _moe(p, h, cfg)
        h = h + o
    elif cfg.d_ff > 0:
        h = h + _mlp(p, h, cfg)
    return h, new_cache


def decode_step(cfg: ModelConfig, params, cache, batch, pos, positions=None,
                block_tables=None):
    """One decode step.  batch: {"tokens": (B, 1)} (or embeds); pos scalar.

    ``pos`` is the shared cache-slot index (the padded coordinate);
    ``positions`` (optional, (B,) int32) are per-sequence real positions for
    ragged left-padded batches (``pos − pad[i]``) — see `_attn_decode`.

    ``block_tables`` ((B, n_logical) int32, optional) selects the paged
    cache layout: ``cache`` holds physical K/V pools shared across slots,
    ``positions`` is the per-slot (B,) logical write position and ``pos``
    the (B,) slot of the attended view (``pos − positions`` shifts each
    slot's keys into the static engine's padded layout; omitted
    ``positions`` ⇒ no shift) — the continuous-batching scheduler's layout,
    DESIGN.md §15.  SSM state stays
    slot-resident (O(1) per slot) and is indexed by batch row as usual.
    Returns (logits (B, vocab) f32, new_cache).
    """
    pos = jnp.asarray(pos)
    if jnp.ndim(pos) and positions is None:
        # per-slot positions with no separate pad vector: slots are packed
        # (scheduler slots carry no left-pad), so real position == pos.
        positions = pos
    if cfg.frontend == "embeddings":
        h = batch["embeds"].astype(dtype_of(cfg))
    else:
        h = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.pos == "sinusoidal":
        pe = (sinusoidal(pos[None], cfg.d_model)[None] if positions is None
              else sinusoidal(positions[:, None], cfg.d_model))
        h = h + pe.astype(h.dtype)

    windows = jnp.asarray(window_array(cfg, FULL_WINDOW))
    if block_tables is not None:
        return _paged_decode(cfg, params, h, windows, cache, pos, positions,
                             block_tables)
    all_stacked = all(_cache_is_stacked(cache[f"sub{i}"])
                      for i in range(cfg.layers_per_block))
    if all_stacked:
        def body(carry, xs):
            hh = carry
            blk, wrow, crow = xs
            new_rows = {}
            for i in range(cfg.layers_per_block):
                hh, nc = _layer_decode(blk[f"sub{i}"], hh, cfg, i, wrow[i],
                                       pos, crow[f"sub{i}"], positions)
                new_rows[f"sub{i}"] = nc
            return hh, new_rows

        cache_xs = {f"sub{i}": cache[f"sub{i}"]
                    for i in range(cfg.layers_per_block)}
        h, new_caches = jax.lax.scan(body, h,
                                     (params["blocks"], windows, cache_xs))
    else:
        # heterogeneous caches: unrolled layer loop (hymba: 32 layers)
        new_caches = {f"sub{i}": {"per_block": []}
                      for i in range(cfg.layers_per_block)}
        for b in range(cfg.n_blocks):
            blk = jax.tree.map(lambda x: x[b], params["blocks"])
            for i in range(cfg.layers_per_block):
                col = cache[f"sub{i}"]
                c = col["per_block"][b] if not _cache_is_stacked(col) \
                    else jax.tree.map(lambda x: x[b], col)
                h, nc = _layer_decode(blk[f"sub{i}"], h, cfg, i,
                                      windows[b, i], pos, c, positions)
                new_caches[f"sub{i}"]["per_block"].append(nc)
        for i in range(cfg.layers_per_block):
            col = cache[f"sub{i}"]
            if _cache_is_stacked(col):
                new_caches[f"sub{i}"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs, 0),
                    *new_caches[f"sub{i}"]["per_block"])

    logits = _lm_head(cfg, params, h)[:, 0]
    return logits, new_caches


def _paged_decode(cfg: ModelConfig, params, h, windows, cache, pos, positions,
                  block_tables):
    """`decode_step` over the paged pool (DESIGN.md §15).

    The K/V pool stays out of the layer scan's carried state: every layer
    reads the step's incoming pool (a gather that takes the layer's index,
    so no layer's pool is sliced out or copied) and gives back only its
    new rows; ONE scatter after the scan writes all layers' rows, in place
    where the caller donates the pool.  SSM leaves ride the scan as
    before.
    """
    subs = [f"sub{i}" for i in range(cfg.layers_per_block)]
    pools = {s: cache[s]["kv"] for s in subs if "kv" in cache[s]}
    step = None                 # pure SSM: the block table is unused
    if pools:
        block = next(iter(pools.values())).shape[2]
        step = _paged_step(block_tables, pos, positions, block)
    # (L, n_phys, block, 2·Hk·dh) as (L, n_phys·block, 2·Hk·dh) rows: a
    # bitcast on the chip where a block fills whole tiles (block 16, bf16)
    flat = {s: p.reshape(p.shape[0], -1, p.shape[-1])
            for s, p in pools.items()}

    def body(carry, xs):
        hh = carry
        blk, wrow, crow, layer = xs
        new_rows = {}
        for i, sub in enumerate(subs):
            c = dict(crow[sub], **({"kv": flat[sub]} if sub in flat else {}))
            hh, nc = _layer_decode(blk[sub], hh, cfg, i, wrow[i], pos, c,
                                   positions, (step, layer))
            new_rows[sub] = nc
        return hh, new_rows

    rest = {s: {n: x for n, x in cache[s].items() if n != "kv"}
            for s in subs}
    h, new_caches = jax.lax.scan(
        body, h, (params["blocks"], windows, rest,
                  jnp.arange(cfg.n_blocks, dtype=jnp.int32)))
    layers = jnp.arange(cfg.n_blocks, dtype=jnp.int32)[:, None]
    for sub, pool in flat.items():
        new_caches[sub]["kv"] = pool.at[layers, step.write[None]].set(
            new_caches[sub]["kv"]).reshape(pools[sub].shape)
    logits = _lm_head(cfg, params, h)[:, 0]
    return logits, new_caches


# ----------------------------------------------------------------- prefill --
def prefill(cfg: ModelConfig, params, batch, smax: int):
    """Forward + cache build.  Returns (last-token logits, cache, pos).

    With ``batch["pad"]`` ((B,) left-pad counts) the prefill is mask-correct
    for ragged prompts: per-sequence positions ``arange(S) − pad[i]`` drive
    RoPE and the attention mask (pad slots are invalid keys), and SSM layers
    zero padded inputs so state/conv caches carry no pad contribution.
    Prompts are right-aligned, so the last-token logits are always real.
    """
    h, positions = _embed(cfg, params, batch)
    valid = _valid_of(positions)
    B, S = h.shape[0], h.shape[1]
    dtype = dtype_of(cfg)
    windows = jnp.asarray(window_array(cfg, S))
    cache = init_cache(cfg, B, smax)

    # run layer by layer (unrolled) so each layer's K/V and SSM state can be
    # written into its cache slot; prefill is a serving-time operation where
    # the S×layer loop cost is dominated by the matmuls anyway.
    kind = _mixer_kind(cfg)
    for b in range(cfg.n_blocks):
        blk = jax.tree.map(lambda x: x[b], params["blocks"])
        for i in range(cfg.layers_per_block):
            layer = b * cfg.layers_per_block + i
            p = blk[f"sub{i}"]
            aux = None
            col = cache[f"sub{i}"]
            c = col["per_block"][b] if not _cache_is_stacked(col) else None

            if kind in ("attn", "hybrid"):
                oa, (k, v) = _attn_full(p, h, cfg, windows[b, i], positions)
            if kind in ("ssm", "hybrid"):
                x = rms_norm(h, p["norm_mix"], cfg.norm_eps)
                os_, ssm_c = _ssm_prefill(p["ssm"], x, cfg, valid)
                if cfg.post_norm:
                    os_ = rms_norm(os_, p["norm_mix_post"], cfg.norm_eps)
            if kind == "attn":
                h = h + oa
            elif kind == "ssm":
                h = h + os_
            else:
                oa2 = rms_norm(oa, p["norm_attn_out"], cfg.norm_eps)
                os2 = rms_norm(os_, p["norm_ssm_out"], cfg.norm_eps)
                h = h + 0.5 * (oa2 + os2)
            if cfg.mlp_kind(i) == "moe":
                o, _ = _moe(p, h, cfg)
                h = h + o
            elif cfg.d_ff > 0:
                h = h + _mlp(p, h, cfg)

            # ---- write caches
            upd = {}
            if kind in ("attn", "hybrid"):
                w = cfg.window_for_layer(layer, smax)
                if w < smax:   # ring
                    L = min(w, S)
                    ts = jnp.arange(S - L, S)
                    slots = jnp.mod(ts, w)
                    ck = jnp.zeros((B, w) + k.shape[2:], dtype)
                    cv = jnp.zeros((B, w) + v.shape[2:], dtype)
                    ck = ck.at[:, slots].set(k[:, S - L:].astype(dtype))
                    cv = cv.at[:, slots].set(v[:, S - L:].astype(dtype))
                    cp = jnp.full((w,), -1, jnp.int32).at[slots].set(
                        ts.astype(jnp.int32))
                    upd.update({"k": ck, "v": cv, "pos": cp})
                else:
                    ck = jnp.zeros((B, smax) + k.shape[2:], dtype)
                    cv = jnp.zeros((B, smax) + v.shape[2:], dtype)
                    ck = jax.lax.dynamic_update_slice(
                        ck, k.astype(dtype), (0, 0, 0, 0))
                    cv = jax.lax.dynamic_update_slice(
                        cv, v.astype(dtype), (0, 0, 0, 0))
                    upd.update({"k": ck, "v": cv})
            if kind in ("ssm", "hybrid"):
                upd["ssm"] = ssm_c
            col = cache[f"sub{i}"]
            if _cache_is_stacked(col):
                cache[f"sub{i}"] = jax.tree.map(
                    lambda full, new: full.at[b].set(new), col, upd)
            else:
                col["per_block"][b] = upd

    logits = _lm_head(cfg, params, h)[:, -1]
    return logits, cache, jnp.int32(S)


def _ssm_prefill(ssm_params, x, cfg, valid=None):
    """SSD forward that also returns the decode cache (state + conv tail).

    ``valid`` ((B, S) bool) zeroes padded inputs exactly like `ssm_apply`:
    pad slots contribute nothing to the running state or the conv tail, so
    decode continues from the same cache a pad-free prefill would build.
    """
    from .ssm import _conv, _gates, _mask_ssm_inputs, _split_proj
    B, S, _ = x.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    proj = jnp.einsum("bsd,de->bse", x, ssm_params["in_proj"])
    z, xBC_raw, dt = _split_proj(cfg, proj)
    xBC_raw = _mask_ssm_inputs(xBC_raw, valid)
    conv_tail = xBC_raw[:, S - (cfg.ssm_conv - 1):, :]
    y = ssm_apply(ssm_params, x, cfg, valid=valid)
    # final state: rerun the recurrence cheaply at chunk granularity
    xBC = _conv(xBC_raw, ssm_params["conv_w"], ssm_params["conv_b"])
    xi = xBC[..., :cfg.d_inner].reshape(B, S, H, P).astype(jnp.float32)
    Bv = xBC[..., cfg.d_inner:cfg.d_inner + N].astype(jnp.float32)
    dt_, dA = _gates(cfg, ssm_params, dt)
    if valid is not None:
        v32 = valid[..., None].astype(jnp.float32)         # (B, S, 1)
        dt_ = dt_ * v32
        dA = dA * v32
    cum = jnp.cumsum(dA, axis=1)
    tail = jnp.exp(cum[:, -1:, :] - cum)
    state = jnp.einsum("bth,btn,bthp->bhnp", tail * dt_, Bv, xi)
    cache = {"state": state,
             "conv": conv_tail.astype(dtype_of(cfg))}
    return y, cache


# -------------------------------------------------------------- accounting --
def count_params(cfg: ModelConfig) -> int:
    """Total parameter count (exact, from shapes)."""
    shapes = jax.eval_shape(
        lambda k: make_params(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def active_params(cfg: ModelConfig) -> int:
    """Active-per-token parameters (MoE: top_k experts + shared + backbone)."""
    total = count_params(cfg)
    if not cfg.moe:
        return total
    f = cfg.moe_d_ff or cfg.d_ff
    per_expert = 3 * cfg.d_model * f if cfg.glu else 2 * cfg.d_model * f
    n_moe_layers = sum(1 for l in range(cfg.num_layers)
                       if cfg.mlp_kind(l) == "moe")
    inactive = n_moe_layers * (cfg.num_experts - cfg.top_k) * per_expert
    return total - inactive
