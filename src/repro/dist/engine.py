"""Engine-side integration: mesh validation + one-time SHARDED weight
encode/placement (DESIGN.md §17).

`serve.Engine` hands its mesh here at construction.  :func:`make_context`
validates the mesh against the config's launch bases (channel layouts need
C % model == 0 for every basis the decode path touches) and returns the
:class:`~repro.dist.context.DistContext` the engine activates around its
jit invocation sites.  :func:`place_params` runs the one-time weight encode
UNDER ``jit(..., out_shardings=...)`` with `launch.sharding.param_specs`'s
rns modes: XLA partitions the encode itself, so under the channel layout
each device forward-converts only its channel slice of every weight — the
full residue pytree never materializes on one device.
"""
from __future__ import annotations

import jax

from .context import DistContext

__all__ = ["make_context", "place_params", "launch_bases"]


def launch_bases(cfg):
    """The distinct RNS bases the config's fused decode launches use
    (derived from `kernels.tune.decode_shapes_for`'s enumeration rules)."""
    from repro.core.rns import basis_for_chain, basis_for_int8_matmul

    spec = cfg.linear_spec
    if not spec.is_rns:
        return []
    d, F = cfg.d_model, cfg.d_ff
    H, Hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    has_attn = cfg.attention != "none" or cfg.hybrid
    bases = {}
    if spec.domain == "residue":
        if has_attn:
            bases[basis_for_int8_matmul(d).moduli] = basis_for_int8_matmul(d)
            wo = basis_for_int8_matmul(H * dh)
            bases[wo.moduli] = wo
        if cfg.glu and F > 0:
            cb = basis_for_chain(F)
            bases[cb.moduli] = cb
    else:
        pairs = set()
        if has_attn:
            pairs |= {d, H * dh}
        if F > 0:
            pairs |= {d, F}
        for K in pairs:
            b = basis_for_int8_matmul(K)
            bases[b.moduli] = b
    return list(bases.values())


def make_context(cfg, mesh, layout: str | None = None) -> DistContext:
    """Build the engine's DistContext, failing fast on hopeless meshes.

    ``layout=None`` takes the config's ``dist_layout`` preference (falling
    back to "auto").  The layout is a per-launch PREFERENCE — launches whose
    C (or N) the axis does not divide fall back individually
    (`rns_shard.sharded_fused_matmul`) — so the only construction-time
    error is a mesh no launch basis can use at all under a forced
    "channel" layout (every C coprime to the axis ⇒ the whole model would
    silently replicate; that is a mis-sized mesh, not a preference).
    """
    spec = cfg.linear_spec
    lay = layout if layout is not None else (
        spec.dist if spec.dist != "none" else "auto")
    ctx = DistContext(mesh=mesh, layout=lay)
    if ctx.nshards > 1 and lay == "channel":
        bases = launch_bases(cfg)
        if bases and all(len(b.moduli) % ctx.nshards for b in bases):
            counts = sorted({len(b.moduli) for b in bases})
            raise ValueError(
                f"dist_layout='channel' on a model axis of size "
                f"{ctx.nshards}, but NO launch basis is divisible (channel "
                f"counts {counts}) — every launch would replicate.  Pick a "
                "model axis dividing one of the counts, or layout="
                "'column'/'auto'")
    return ctx


def place_params(ctx: DistContext, cfg, params, *, group_basis=None):
    """One-time weight encode + placement on the context's mesh.

    Encode-weights configs run `core.rns_tensor.encode_params` as a JITTED
    function with ``out_shardings`` from `launch.sharding.param_specs`
    (mode rns_tp / rns_tp_col / rns_tp_auto by layout): the residue stacks
    come out of the encode already sharded — each device forward-converts
    only its slice — and every non-RNS leaf (embed, lm_head, norms)
    replicates.  Non-encoding configs just device_put the raw pytree
    replicated (the fused launches re-shard their operands per launch via
    shard_map in_specs).
    """
    from repro.core.rns_tensor import encode_params
    from repro.launch.sharding import param_specs, shardings

    spec = cfg.linear_spec
    # placement affects locality only (each launch's shard_map in_specs
    # re-shard operands regardless), so the channel preference places via
    # the tolerant "rns_tp_auto" mode — a C=5 leaf in a channel-layout
    # model replicates instead of raising the strict "rns_tp" error.
    mode = "rns_tp_col" if ctx.layout == "column" else "rns_tp_auto"
    if spec.is_rns and spec.encode_weights:
        # The jnp forward converter, not the spec's Pallas one: XLA cannot
        # partition a Mosaic kernel (only a shard_map body may hold one),
        # and integer mod gives the same residues either way.
        def enc(p):
            return encode_params(p, backend="jnp", group_basis=group_basis)

        shapes = jax.eval_shape(enc, params)
        out = shardings(ctx.mesh, param_specs(ctx.mesh, cfg, shapes, mode))
        return jax.jit(enc, out_shardings=out)(params)
    return jax.device_put(
        params, shardings(ctx.mesh, param_specs(ctx.mesh, cfg, params, mode)))
