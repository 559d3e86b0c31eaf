"""Sharding policy rules + dry-run integration (subprocess with 32 devices)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, get_config
from repro.launch.inputs import abstract_cache, abstract_params, input_specs
from repro.launch.mesh import dp_axes, make_host_mesh
from repro.launch.sharding import (batch_specs, cache_specs, mode_for,
                                   param_specs)


class FakeMesh:
    """Shape-only stand-in (rule tests need no real devices)."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        n = 1
        for v in self.shape.values():
            n *= v
        return n


MESH = FakeMesh({"data": 16, "model": 16})


def test_tp_rules():
    cfg = get_config("smollm-135m")
    specs = param_specs(MESH, cfg, abstract_params(cfg), "tp")
    blocks = specs["blocks"]["sub0"]
    assert blocks["attn"]["wq"] == P(None, None, "model")
    assert blocks["attn"]["wo"] == P(None, "model", None)
    assert blocks["mlp"]["w_gate"] == P(None, None, "model")
    assert blocks["mlp"]["w_down"] == P(None, "model", None)
    assert specs["embed"] == P("model", None)        # 49152 % 16 == 0
    assert blocks["norm_mix"] == P(None, None)       # (L, d) stacked, replic.


def test_fsdp_rules():
    cfg = get_config("yi-34b")
    specs = param_specs(MESH, cfg, abstract_params(cfg), "fsdp_tp")
    blocks = specs["blocks"]["sub0"]
    assert blocks["attn"]["wq"] == P(None, ("data",), "model")
    assert blocks["mlp"]["w_down"] == P(None, "model", ("data",))


def test_moe_expert_parallel():
    cfg = get_config("llama4-maverick-400b-a17b")
    specs = param_specs(MESH, cfg, abstract_params(cfg), "fsdp_tp")
    moe = specs["blocks"]["sub1"]["moe"]
    assert moe["w_gate"] == P(None, "model", ("data",), None)   # E over model
    assert moe["router"] == P(None, None, None)


def test_odd_vocab_fallback():
    """hymba's vocab 32001 can't shard 16 ways: falls back to d-sharding."""
    cfg = get_config("hymba-1.5b")
    specs = param_specs(MESH, cfg, abstract_params(cfg), "tp")
    assert specs["embed"] == P(None, "model")        # (V, d): d sharded


def test_optimizer_state_inherits():
    from repro.train.optimizer import make_optimizer
    cfg = get_config("smollm-135m")
    pa = abstract_params(cfg)
    opt = make_optimizer(cfg)
    oa = jax.eval_shape(opt.init, pa)
    specs = param_specs(MESH, cfg, oa, "tp")
    assert specs["m"]["blocks"]["sub0"]["attn"]["wq"] == P(None, None, "model")


def test_cache_specs_sequence_sharded():
    cfg = get_config("yi-34b")
    cache = abstract_cache(cfg, 128, 32768)
    specs = cache_specs(MESH, cfg, cache)
    assert specs["sub0"]["k"] == P(None, ("data",), "model", None, None)


def test_batch_specs_divisibility():
    cfg = get_config("mamba2-1.3b")
    b = input_specs(cfg, SHAPES["train_4k"])
    specs = batch_specs(MESH, cfg, b)
    assert specs["tokens"] == P(("data",), None)
    b1 = input_specs(cfg, SHAPES["long_500k"])
    specs1 = batch_specs(MESH, cfg, b1)
    assert specs1["tokens"] == P(None, None)          # B=1 unshardable


def test_mode_for_size_threshold():
    assert mode_for(get_config("smollm-135m")) == "tp"
    assert mode_for(get_config("yi-34b")) == "fsdp_tp"


@pytest.mark.slow
def test_dryrun_subprocess_cell():
    """End-to-end dry-run of one cell in a fresh interpreter (512 devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = "/tmp/pytest_dryrun.jsonl"
    if os.path.exists(out):
        os.remove(out)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-135m",
         "--shape", "decode_32k", "--out", out],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(open(out).read().strip().split("\n")[-1])
    assert rec["status"] == "ok", rec.get("error")
    assert rec["n_devices"] == 256
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")


# ======================================================== rns dist modes ====
# repro.dist placement (DESIGN.md §17): encoded RNSTensor leaves shard over
# "model", everything float replicates (bit-identity keeps float reductions
# whole).  FakeMesh suffices — the rules read only shapes and axis sizes.

MESH_42 = FakeMesh({"data": 4, "model": 2})


def _rns_tree(N=12, stacked=True):
    """A stacked (L, C, K, N) encoded weight + a float leaf, C = 4."""
    from repro.core.rns import basis_for_int8_matmul
    from repro.core.rns_tensor import RNSTensor

    b = basis_for_int8_matmul(8)
    C = len(b.moduli)
    shape = (3, C, 8, N) if stacked else (C, 8, N)
    wt = RNSTensor(residues=jnp.zeros(shape, jnp.int16),
                   scale=jnp.zeros(shape[:-3] + (1, N), jnp.float32),
                   basis=b, bound=127, signed=True)
    return {"w": wt, "norm": jnp.zeros((8,), jnp.float32)}, C


def test_rns_tp_shards_channel_axis():
    cfg = get_config("smollm-135m")
    tree, C = _rns_tree()
    assert C % 2 == 0
    specs = param_specs(MESH_42, cfg, tree, "rns_tp")
    # channel axis is −3 of the (L, C, K, N) stack; scale stays whole
    assert specs["w"].residues == P(None, "model", None, None)
    assert specs["w"].scale == P(None, None, None)
    assert specs["norm"] == P(None)                  # float leaves replicate


def test_rns_tp_strict_rejects_indivisible_channels():
    cfg = get_config("smollm-135m")
    tree, C = _rns_tree()
    bad = FakeMesh({"data": 4, "model": 3})          # 3 does not divide C=4
    with pytest.raises(ValueError, match="channel count"):
        param_specs(bad, cfg, tree, "rns_tp")


def test_rns_tp_col_shards_columns_and_scale():
    cfg = get_config("smollm-135m")
    tree, _ = _rns_tree(N=12)
    specs = param_specs(MESH_42, cfg, tree, "rns_tp_col")
    assert specs["w"].residues == P(None, None, None, "model")
    assert specs["w"].scale == P(None, None, "model")  # (L, 1, N) follows N


def test_rns_tp_auto_prefers_channels_then_columns_then_replicates():
    cfg = get_config("smollm-135m")
    tree, _ = _rns_tree(N=12)
    specs = param_specs(MESH_42, cfg, tree, "rns_tp_auto")
    assert specs["w"].residues == P(None, "model", None, None)   # C wins
    mesh3 = FakeMesh({"data": 4, "model": 3})        # C=4 no, N=12 yes
    specs = param_specs(mesh3, cfg, tree, "rns_tp_auto")
    assert specs["w"].residues == P(None, None, None, "model")
    assert specs["w"].scale == P(None, None, "model")
    tree10, _ = _rns_tree(N=10)                      # neither divides by 3
    specs = param_specs(mesh3, cfg, tree10, "rns_tp_auto")
    assert specs["w"].residues == P(None, None, None, None)
    assert specs["w"].scale == P(None, None, None)


def test_cache_specs_paged_pool_sharding():
    """Paged pools shard the independent physical-block axis, never the
    block contents."""
    cfg = get_config("smollm-135m")
    pool = {"sub0": {
        # (L, n_phys, bs, 2·Hk·dh): a token's K then V in one row
        "kv": jnp.zeros((2, 32, 16, 2 * 3 * 8), jnp.float32),
        "pos": jnp.zeros((8,), jnp.int32),
    }}
    specs = cache_specs(MESH, cfg, pool, paged=True)
    assert specs["sub0"]["kv"] == P(None, ("data",), None, None)
    assert specs["sub0"]["pos"] == P(None)
    # the dense rule (paged=False) knows no pool and would replicate it
    dense = cache_specs(MESH, cfg, pool, paged=False)
    assert dense["sub0"]["kv"] != specs["sub0"]["kv"]
