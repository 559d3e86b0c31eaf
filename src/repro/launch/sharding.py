"""Sharding policy: name/shape-driven PartitionSpecs for params, optimizer
state, batches and decode caches (DESIGN.md §6).

Two modes:
  tp       — Megatron 1-D tensor parallel over "model" + data parallel over
             the dp axes.  Default for ≤4B-param models.
  fsdp_tp  — tp plus ZeRO-3-style weight sharding: each weight's largest
             non-TP dim is additionally sharded over the dp axes; optimizer
             state inherits the param specs.  Default for larger models.

Every rule degrades gracefully: an axis is only assigned to a dim when the
dim size divides the axis size product (`_maybe`), so odd head counts /
vocab sizes (hymba 32001, mamba2 50280…) fall back instead of failing —
GSPMD then pads or re-shards locally, which the roofline notes account for.

KV caches shard the *sequence* dim over "model" (flash-decoding layout):
softmax over a sequence-sharded axis lowers to cheap per-row all-reduces and
sidesteps all head-divisibility issues; 32k/500k caches scale across chips.
SSM states shard the state dim N over "model"; batch over dp axes whenever
divisible (long_500k's B=1 stays unsharded — single-stream decode has no
data parallelism, visible in its roofline).
"""
from __future__ import annotations

import re
from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from .mesh import MODEL_AXIS, dp_axes

__all__ = ["param_specs", "batch_specs", "cache_specs", "logits_spec",
           "shardings", "mode_for"]


def mode_for(cfg: ModelConfig) -> str:
    """Default distribution mode by model size (params in bf16)."""
    from repro.models.transformer import count_params
    return "fsdp_tp" if count_params(cfg) > 4e9 else "tp"


def _axis_size(mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _maybe(mesh, axes, dim: int):
    """axes if dim divides their size product, else None (replicate dim)."""
    if axes is None or dim <= 0:
        return None
    if dim % _axis_size(mesh, axes) == 0:
        return axes
    return None


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _param_rule(mesh, mode: str, path: str, shape: Tuple[int, ...]):
    if mode == "dp":
        # pure data parallelism: params fully replicated (small models —
        # TP16 on a 135M model wastes the MXU and pays L·6 activation
        # all-reduces; see EXPERIMENTS.md §Perf cell A)
        return P(*([None] * len(shape)))
    dp = dp_axes(mesh)
    fsdp = dp if mode == "fsdp_tp" else None
    mdl = MODEL_AXIS
    nd = len(shape)
    name = path.rsplit("/", 1)[-1]

    def spec(*ax):
        return P(*[_maybe(mesh, a, d) for a, d in zip(ax, shape)])

    if name == "embed":                              # (V, d)
        s = spec(mdl, fsdp)
        if s[0] is None:                             # odd vocab: shard d
            return spec(fsdp, mdl)
        return s
    if name == "lm_head":                            # (d, V)
        s = spec(fsdp, mdl)
        if s[-1] is None:
            return spec(mdl, fsdp)
        return s
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "in_proj"):
        if nd == 3:                                  # (L, d_in, d_out) col-par
            return spec(None, fsdp, mdl)
        if nd == 4:                                  # (L, E, d, f) experts: EP
            return spec(None, mdl, fsdp, None)
        return spec(fsdp, mdl)                       # (d_in, d_out) unstacked
    if name in ("wo", "w_down", "out_proj"):
        if nd == 3:                                  # (L, d_in, d_out) row-par
            return spec(None, mdl, fsdp)
        if nd == 4:                                  # (L, E, f, d)
            return spec(None, mdl, fsdp, None)
        return spec(mdl, fsdp)
    if name == "conv_w":                             # (L, k, conv_dim)
        return spec(None, None, mdl)
    if name == "router":                             # (L, d, E): tiny, replic.
        return P(*([None] * nd))
    # ---- fallback (norm scales, biases, A_log, optimizer vr/vc, …):
    if nd <= 1 or mode != "fsdp_tp":
        return P(*([None] * nd))
    # FSDP fallback: shard the largest dim that divides the dp axes
    sizes = list(shape)
    order = sorted(range(nd), key=lambda i: -sizes[i])
    out = [None] * nd
    for i in order:
        if sizes[i] % _axis_size(mesh, dp) == 0 and sizes[i] >= 1024:
            out[i] = dp
            break
    return P(*out)


def _rns_param_specs(mesh, tree, mode: str):
    """Distributed-serving placement for encoded pytrees (repro.dist, §17).

    :class:`~repro.core.rns_tensor.RNSTensor` leaves shard over "model" —
    the residue channel axis at −3 for ``"rns_tp"`` (strict: raises when the
    axis size does not divide C, because a channel-sharded launch cannot
    split a modulus) or the output-column axis at −1 for ``"rns_tp_col"``
    (whose per-column scale shards along) — and EVERY other leaf replicates:
    the bit-identity contract keeps the float einsums (embed, lm_head,
    norms) whole, so GSPMD never re-associates a float reduction.
    ``"rns_tp_auto"`` prefers channels per leaf and falls back to columns,
    then replication.
    """
    from repro.core.rns_tensor import RNSTensor

    mdl = MODEL_AXIS
    n = _axis_size(mesh, mdl)

    def is_rns(x):
        return isinstance(x, RNSTensor)

    def rep(x):
        return P(*([None] * len(x.shape)))

    def rule(leaf):
        if not is_rns(leaf):
            return rep(leaf)
        res, scale = leaf.residues, leaf.scale
        nd = len(res.shape)
        C, N = res.shape[-3], res.shape[-1]

        def at(pos):                      # position counted from the end
            out = [None] * nd
            out[nd + pos] = mdl
            return P(*out)

        r_spec, s_spec = rep(res), (None if scale is None else rep(scale))
        if mode == "rns_tp":
            if C % n:
                raise ValueError(
                    f"mesh '{mdl}' size {n} does not divide the residue "
                    f"channel count C={C}; channel sharding (rns_tp) needs "
                    "C % model == 0")
            r_spec = at(-3)
        elif mode == "rns_tp_col" and N % n == 0:
            r_spec = at(-1)
            if scale is not None:         # (…, 1, N) per-column scale
                s = [None] * len(scale.shape)
                s[-1] = mdl
                s_spec = P(*s)
        elif mode == "rns_tp_auto":
            if C % n == 0:
                r_spec = at(-3)
            elif N % n == 0:
                r_spec = at(-1)
                if scale is not None:
                    s = [None] * len(scale.shape)
                    s[-1] = mdl
                    s_spec = P(*s)
        # spec tree mirrors the value tree (RNSTensor is a registered
        # pytree): out_shardings/device_put descend it leaf-for-leaf
        return RNSTensor(residues=r_spec, scale=s_spec, basis=leaf.basis,
                         bound=leaf.bound, signed=leaf.signed)

    return jax.tree_util.tree_map(rule, tree, is_leaf=is_rns)


def param_specs(mesh, cfg: ModelConfig, tree, mode: str | None = None):
    """PartitionSpec pytree for params OR optimizer state (same rules —
    optimizer leaves carry the param's path suffix, so m/v inherit the param
    layout and Adafactor's vr/vc hit the shape-driven fallback).

    The ``rns_tp`` / ``rns_tp_col`` / ``rns_tp_auto`` modes place ENCODED
    serving pytrees for `repro.dist` (residue channel / output column axis
    over "model", everything else replicated — see `_rns_param_specs`).
    """
    mode = mode or mode_for(cfg)
    if mode in ("rns_tp", "rns_tp_col", "rns_tp_auto"):
        return _rns_param_specs(mesh, tree, mode)

    def rule(path, leaf):
        return _param_rule(mesh, mode, _path_str(path), tuple(leaf.shape))

    return jax.tree_util.tree_map_with_path(rule, tree)


def batch_specs(mesh, cfg: ModelConfig, batch_tree, mode: str | None = None):
    """tokens/labels (B, S) and embeds (B, S, d): batch over dp axes
    (over *all* axes in pure-dp mode)."""
    dp = dp_axes(mesh)
    if mode == "dp":
        dp = dp + (MODEL_AXIS,)

    def rule(path, leaf):
        b = leaf.shape[0]
        first = _maybe(mesh, dp, b)
        return P(*([first] + [None] * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map_with_path(rule, batch_tree)


def cache_specs(mesh, cfg: ModelConfig, cache_tree, *, paged: bool = False):
    """Decode caches: KV sequence-sharded over "model", SSM state-sharded.

    ``paged=True`` reads the tree as `serve.paged_cache`'s pool layout —
    one ``kv`` leaf (L, n_phys, block_size, 2·Hk·dh) a column.  Paged pools
    shard the independent physical-block axis and keep block contents
    whole.
    """
    dp = dp_axes(mesh)
    mdl = MODEL_AXIS

    def rule(path, leaf):
        p = _path_str(path)
        shape = leaf.shape
        name = p.rsplit("/", 1)[-1]
        if paged and name == "kv":
            # (L, n_phys, block_size, 2·Hk·dh): blocks are independent
            # (the trash block rides along); never split inside a block
            return P(None, _maybe(mesh, dp, shape[1]), None, None)
        # stacked: (L, B, S, Hk, dh); per_block: (B, S, Hk, dh)
        stacked = shape and len(shape) in (5,) and name in ("k", "v")
        if name in ("k", "v"):
            if len(shape) == 5:
                L, B, S = shape[0], shape[1], shape[2]
                return P(None, _maybe(mesh, dp, B), _maybe(mesh, mdl, S),
                         None, None)
            B, S = shape[0], shape[1]
            return P(_maybe(mesh, dp, B), _maybe(mesh, mdl, S), None, None)
        if name == "state":                      # (L?, B, H, N, P)
            if len(shape) == 5:
                return P(None, _maybe(mesh, dp, shape[1]), None,
                         _maybe(mesh, mdl, shape[3]), None)
            return P(_maybe(mesh, dp, shape[0]), None,
                     _maybe(mesh, mdl, shape[2]), None)
        if name == "conv":                       # (L?, B, k-1, conv_dim)
            if len(shape) == 4:
                return P(None, _maybe(mesh, dp, shape[1]), None,
                         _maybe(mesh, mdl, shape[3]))
            return P(_maybe(mesh, dp, shape[0]), None,
                     _maybe(mesh, mdl, shape[2]))
        return P(*([None] * len(shape)))         # pos arrays etc.

    return jax.tree_util.tree_map_with_path(rule, cache_tree)


def logits_spec(mesh, cfg: ModelConfig, batch: int):
    dp = dp_axes(mesh)
    return P(_maybe(mesh, dp, batch), _maybe(mesh, MODEL_AXIS, cfg.vocab_size))


def shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
