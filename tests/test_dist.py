"""Multi-device sharded serving: the DESIGN.md §17 bit-identity contract.

Every test here needs >= 8 devices — CI's multi-device job provides them on
a plain CPU host via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the whole point of the host-mesh platform: the sharding contract is
*bit-identity*, so a fake mesh of host devices proves as much as real
hardware, minus the interconnect timings).

Three layers of the contract:

  * launch — `dist.rns_shard.sharded_fused_matmul` vs the single-device
    `kernels.rns_fused.rns_fused_matmul`, both layouts, float and
    residue-emitting launches;
  * engine — `serve.Engine(mesh=...)` greedy decode bit-identical to the
    unsharded engine for a dense (fused) and a residue-resident config,
    BOTH layouts, scan and host orchestration;
  * wire — the channel-sharded decode jaxpr, audited by the static-analysis
    walker: the only collectives are psums of post-MRC limb planes / float
    outputs; a residue slab on the interconnect is a hard failure
    (`analysis.check_reduced_wire`).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.analysis as analysis
from repro.configs.base import get_smoke_config
from repro.core import rns_tensor as rt
from repro.core.rns import basis_for_int8_matmul
from repro.dist import context as dc
from repro.dist.context import DistContext
from repro.dist.engine import launch_bases, make_context
from repro.dist.rns_shard import crt_tables, sharded_fused_matmul
from repro.kernels.rns_fused import rns_fused_matmul
from repro.models import transformer as T
from repro.serve.engine import Engine

multi = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >= 8 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

PROMPTS = [[5, 6, 7, 8, 9], [3, 1, 4, 1, 5, 9, 2, 6], [2, 7]]
NEW_TOKENS = 8


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(model=2)


# one unsharded reference generation per arch, shared across layout params
_REF = {}


def _reference(arch):
    if arch not in _REF:
        cfg = get_smoke_config(arch)
        params = T.make_params(cfg, jax.random.PRNGKey(0))
        out = Engine(cfg, params, smax=64).generate(
            PROMPTS, max_new_tokens=NEW_TOKENS)
        _REF[arch] = (cfg, params, out)
    return _REF[arch]


# ================================================== launch-level parity ====
@multi
@pytest.mark.parametrize("layout", ["channel", "column"])
@pytest.mark.parametrize("emit", ["float", "residues"])
def test_sharded_launch_bit_identical(mesh, layout, emit):
    """sharded_fused_matmul == rns_fused_matmul, bit for bit, per layout."""
    basis = basis_for_int8_matmul(64)           # C = 4, divisible by model=2
    rng = np.random.default_rng(0)
    xa = rt.encode_activation(
        jnp.asarray(rng.normal(size=(8, 64)), jnp.float32), basis)
    wt = rt.encode(jnp.asarray(rng.normal(size=(64, 32)), jnp.float32), basis)
    ctx = DistContext(mesh=mesh, layout=layout)

    scol = wt.scale if emit == "residues" else None   # requantize constant
    ref = rns_fused_matmul(xa, wt, emit=emit, scale_col=scol)
    got = sharded_fused_matmul(xa, wt, ctx=ctx, emit=emit, scale_col=scol)
    if emit == "residues":
        np.testing.assert_array_equal(np.asarray(got.residues),
                                      np.asarray(ref.residues))
        np.testing.assert_array_equal(np.asarray(got.scale),
                                      np.asarray(ref.scale))
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multi
def test_sharded_launch_no_context_is_plain(mesh):
    """Without an active DistContext the sharded entry IS the plain kernel."""
    basis = basis_for_int8_matmul(64)
    rng = np.random.default_rng(1)
    xa = rt.encode_activation(
        jnp.asarray(rng.normal(size=(4, 64)), jnp.float32), basis)
    wt = rt.encode(jnp.asarray(rng.normal(size=(64, 16)), jnp.float32), basis)
    assert dc.current() is None
    np.testing.assert_array_equal(
        np.asarray(sharded_fused_matmul(xa, wt)),
        np.asarray(rns_fused_matmul(xa, wt)))


# ================================================== engine-level parity ====
@multi
@pytest.mark.parametrize("layout", ["channel", "column"])
@pytest.mark.parametrize("arch", ["rns-smollm-135m-fused",
                                  "rns-smollm-135m-resident"])
def test_engine_sharded_bit_identical(mesh, arch, layout):
    """The acceptance pin: sharded greedy decode == single-device, both
    layouts, for a dense AND a residue-resident config."""
    cfg, params, ref = _reference(arch)
    eng = Engine(cfg, params, smax=64, mesh=mesh, dist_layout=layout)
    got = eng.generate(PROMPTS, max_new_tokens=NEW_TOKENS)
    assert got == ref


@multi
def test_engine_sharded_host_orchestration(mesh):
    """The per-token host loop shares decode_step, so it must shard too."""
    cfg, params, ref = _reference("rns-smollm-135m-resident")
    eng = Engine(cfg, params, smax=64, mesh=mesh, dist_layout="channel")
    got = eng.generate(PROMPTS, max_new_tokens=NEW_TOKENS, engine="host")
    assert got == ref


@multi
def test_engine_layout_from_config_spec(mesh):
    """`rns-smollm-135m-sharded` carries its layout in the LinearSpec; an
    Engine given only a mesh picks it up and still matches the unsharded
    fused reference bit for bit."""
    cfg = get_smoke_config("rns-smollm-135m-sharded")
    assert cfg.linear_spec.dist == "channel"
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    ref = Engine(get_smoke_config("rns-smollm-135m-fused"), params,
                 smax=64).generate(PROMPTS, max_new_tokens=NEW_TOKENS)
    got = Engine(cfg, params, smax=64, mesh=mesh).generate(
        PROMPTS, max_new_tokens=NEW_TOKENS)
    assert got == ref


@multi
def test_engine_rejects_layout_without_mesh():
    cfg = get_smoke_config("rns-smollm-135m-fused")
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="mesh"):
        Engine(cfg, params, smax=64, dist_layout="channel")


# ======================================================= wire contract ====
@multi
def test_channel_decode_wire_is_reduced(mesh):
    """Audit the ACTUAL sharded decode jaxpr: under the channel layout the
    only integer stacks on the interconnect are post-MRC limb planes —
    `check_reduced_wire` must pass with the launch bases' channel counts
    banned and their limb counts whitelisted, and at least one psum must be
    present (the invariant must not hold vacuously)."""
    cfg = get_smoke_config("rns-smollm-135m-resident")
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    ctx = make_context(cfg, mesh, layout="channel")
    cache = T.init_cache(cfg, 2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    with dc.use(ctx):
        jaxpr = jax.make_jaxpr(
            lambda c, t: T.decode_step(cfg, params, c, {"tokens": t}, 4)
        )(cache, tok)

    summ = analysis.summarize(jaxpr)
    assert any(name == "psum" for name, _ in summ.collectives), (
        "channel-sharded decode traced with no psum — the shard_map "
        "region never materialized")
    bases = launch_bases(cfg)
    channels = {len(b.moduli) for b in bases}
    limbs = {crt_tables(b)[2] for b in bases}
    rep = analysis.check_reduced_wire(summ, channels, nlimbs=limbs,
                                      subject="decode/channel")
    assert rep.ok, str(rep.findings)


# ============================================ single-device, no mesh needed ==
@pytest.mark.parametrize("layout,nshards,N,want", [
    ("auto", 4, 576, "column"),         # smollm's C=5 on a v5e 2x2
    ("channel", 4, 576, "column"),      # 5 % 4 → the other layout
    ("channel", 5, 576, "channel"),
    ("column", 4, 30, "replicate"),     # neither C nor N divides
])
def test_resolve_layout_per_launch_fallback(layout, nshards, N, want):
    from repro.dist.rns_shard import resolve_layout

    basis = basis_for_int8_matmul(576)
    assert len(basis.moduli) == 5
    assert resolve_layout(layout, nshards, basis, 4, N) == want


def test_host_mesh_axes_are_auto():
    """`jax.make_mesh` defaults to Explicit axes since JAX 0.9; the model's
    un-annotated cache writes need Auto ones."""
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model=1)
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)
