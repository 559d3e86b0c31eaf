"""Compile the serving path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached.  These compiles catch what interpret mode
cannot — blocks below the native tile, VMEM overflows, unsupported in-kernel
ops, kernels XLA cannot place — at smollm-135m's real widths.  Nothing runs:
a pass here says the chip's compiler accepts the program, not that its
results are right (that is `chip_smoke.py`'s job on the chip).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.conversion_plan import ConversionPlan
from repro.core.rns import basis_for_chain, basis_for_int8_matmul
from repro.core.rns_tensor import RNSTensor

D, F = 576, 1536                # smollm-135m d_model, d_ff
DECODE_M, PREFILL_M = 4, 1024   # 4 slots; 4 lanes × the 256-token bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one — keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _native(fn, *args):
    """Compile ``fn`` for the described chip; assert a Mosaic kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _tensor(shape, basis, chip, scale=None):
    return RNSTensor(residues=jax.ShapeDtypeStruct(shape, jnp.int8,
                                                   sharding=chip),
                     scale=scale, basis=basis, bound=127, signed=True)


VARIANTS = ("quantize+encoded", "residue-in", "residue-in+gate",
            "emit-residues")
# the megakernel's custom call as a v5e trace names it: the name
# `bench/metrics/megakernel_roofline.py` matches, pinned by the
# pallas_call's `name=` whatever the Python wrapper is called
KERNEL_CALL = re.compile(r"^\s*(ROOT )?%_fused_call\.\d+ = .*custom-call\(.*"
                         r'custom_call_target="tpu_custom_call"', re.M)


@pytest.mark.parametrize("M", [DECODE_M, PREFILL_M])
@pytest.mark.parametrize("variant", VARIANTS)
def test_megakernel_variant_compiles_for_v5e(one_chip, variant, M):
    from repro.kernels import rns_fused_matmul

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b5, b7 = basis_for_int8_matmul(D), basis_for_chain(F)
    launch = functools.partial(rns_fused_matmul, interpret=False)
    if variant == "quantize+encoded":            # float-domain linear, C=5
        text = _native(lambda x, w, s, c: launch(x, w, quantize=True,
                                                 scale_row=s, scale_col=c),
                       arr((M, D)), _tensor((5, D, F), b5, one_chip),
                       arr((M, 1)), arr((1, F)))
    elif variant == "residue-in":                # chain gate/up, C=7
        text = _native(lambda x, w, c: launch(x, w, scale_col=c),
                       _tensor((7, M, D), b7, one_chip, arr((M, 1))),
                       _tensor((7, D, F), b7, one_chip), arr((1, F)))
    elif variant == "residue-in+gate":           # chain down, C=7
        text = _native(lambda x, g, w, c: launch(x, w, gate=g, scale_col=c),
                       _tensor((7, M, F), b7, one_chip, arr((M, 1))),
                       arr((M, F), jnp.int8),
                       _tensor((7, F, D), b7, one_chip), arr((1, D)))
    else:                                        # chain up exit, C=7
        text = _native(lambda x, w, c: launch(x, w, emit="residues",
                                              scale_col=c).residues,
                       _tensor((7, M, D), b7, one_chip, arr((M, 1))),
                       _tensor((7, D, F), b7, one_chip), arr((1, F)))
    assert KERNEL_CALL.search(text)


@pytest.mark.parametrize("shape", [(DECODE_M, D), (30, D, F)])
def test_forward_conversion_compiles_for_v5e(one_chip, shape):
    """The activation encode of a chain and the weight encode at load."""
    from repro.kernels.rns_convert import rns_forward

    moduli = basis_for_chain(F).moduli
    _native(lambda x: rns_forward(x, moduli, interpret=False),
            jax.ShapeDtypeStruct(shape, jnp.int8, sharding=one_chip))


def test_reverse_conversion_compiles_for_v5e(one_chip):
    from repro.kernels.rns_convert import rns_reverse

    plan = ConversionPlan.for_basis(basis_for_chain(F))
    _native(lambda r, s: rns_reverse(r, plan, scale=s, interpret=False),
            jax.ShapeDtypeStruct((7, DECODE_M, D), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((DECODE_M, D), jnp.float32,
                                 sharding=one_chip))


def test_resident_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """One full-width decode step of `rns-smollm-135m-resident` (30 layers,
    vocab 49152) from `jax.eval_shape` shapes: every kernel the step
    launches compiles natively.  The kernels choose interpret mode by the
    default backend, which is the CPU here, so the test points it at TPU."""
    from repro.configs.base import get_config
    from repro.core.rns_tensor import encode_params
    from repro.models import transformer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("rns-smollm-135m-resident")

    def params():
        return encode_params(T.make_params(cfg, jax.random.PRNGKey(0)),
                             backend=cfg.linear_spec.backend,
                             group_basis={"mlp": basis_for_chain(cfg.d_ff)})

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    text = _native(
        lambda p, c, tok, pos: T.decode_step(cfg, p, c, {"tokens": tok}, pos),
        placed(jax.eval_shape(params)),
        placed(jax.eval_shape(lambda: T.init_cache(cfg, DECODE_M, 512))),
        jax.ShapeDtypeStruct((DECODE_M, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    # the layer scan body holds the stacked-QKV chain, the wo exit, the MLP
    # chain's gate/up/down launches and the two activation encodes
    assert text.count("tpu_custom_call") >= 5


def test_sharded_fused_decode_step_compiles_for_v5e_2x2(topo, one_chip,
                                                        monkeypatch):
    """The `chip_smoke.py --four-chips` program: one decode step of
    `rns-smollm-135m-fused` on a (1, 4) mesh of the 2x2 with
    ``dist_layout="auto"``.  C=5 divides no axis of 4, so every launch
    takes the column layout, and each Mosaic kernel sits inside a
    `shard_map` — XLA refuses to partition one outside it."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import get_config
    from repro.core.rns_tensor import encode_params
    from repro.dist.context import DistContext, use
    from repro.launch.sharding import param_specs, shardings
    from repro.models import transformer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("rns-smollm-135m-fused")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, P())

    shapes = jax.eval_shape(lambda: encode_params(
        T.make_params(cfg, jax.random.PRNGKey(0)), backend="jnp"))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, shardings(mesh, param_specs(mesh, cfg, shapes,
                                            "rns_tp_auto")))
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: T.init_cache(cfg, DECODE_M, 256)))
    with use(DistContext(mesh=mesh, layout="auto")):
        text = _native(
            lambda p, c, tok, pos: T.decode_step(cfg, p, c,
                                                 {"tokens": tok}, pos),
            params, cache,
            jax.ShapeDtypeStruct((DECODE_M, 1), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    # the column layout's bitwise gather is a psum of scattered columns
    assert "all-reduce" in text


POOL_SHAPES = re.compile(r"= \w+\[([\d,]*)\]\{[^}]*\} (copy|transpose|reshape|"
                         r"dynamic-update-slice|dynamic-slice)\(")


@pytest.mark.parametrize("arch", ["smollm-135m", "rns-smollm-135m-fused"])
def test_chunk_program_writes_the_pool_in_place_for_v5e(one_chip, monkeypatch,
                                                        arch):
    """The served decode chunk at the benchmark's size (32 slots, block 16,
    1,153 blocks, 8 steps): no op copies, relayouts, slices or rewrites a
    K/V pool, a layer's or the stacked one — each layer gathers from the
    step's incoming pool and one scatter after the layer scan writes the
    step's rows — and the donated pool is aliased to the returned one."""
    import math

    from repro.configs.base import get_config
    from repro.core.rns_tensor import encode_params
    from repro.models import transformer as T
    from repro.serve.paged_cache import init_paged_cache
    from repro.serve.scheduler import chunk_program

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config(arch)
    slots, block, n_blocks, nlog = 32, 16, 1153, 36
    spec = cfg.linear_spec

    def params():
        p = T.make_params(cfg, jax.random.PRNGKey(0))
        return (encode_params(p, backend=spec.backend) if spec.encode_weights
                else p)

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p_shapes = placed(jax.eval_shape(params))
    pool = placed(jax.eval_shape(
        lambda: init_paged_cache(cfg, n_blocks, block, slots)))
    text = chunk_program(cfg, 8).lower(
        p_shapes, pool, arg((slots, nlog), jnp.int32),
        *(arg((slots,), jnp.int32),) * 2, arg((slots,), jnp.bool_),
        arg((slots,), jnp.int32), arg((slots, 2), jnp.uint32),
        arg((), jnp.float32), arg((), jnp.int32)).compile().as_text()

    # one layer's K or V, both, and the stacked pool of either
    per_layer = n_blocks * block * cfg.num_kv_heads * cfg.head_dim
    pool_sizes = {n * per_layer for n in (1, 2, cfg.n_blocks,
                                          2 * cfg.n_blocks)}
    bad = [m.group(0) for m in POOL_SHAPES.finditer(text)
           if math.prod(int(d) for d in m.group(1).split(",") if d)
           in pool_sizes]
    assert not bad, bad
    assert " scatter(" in text

    # the pool leaves are parameters after the weights, outputs after cur
    # and done: each is donated into its own output
    first = len(jax.tree.leaves(p_shapes))
    aliases = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)",
                              text))
    for i in range(len(jax.tree.leaves(pool))):
        assert aliases.get(str(2 + i)) == str(first + i)
