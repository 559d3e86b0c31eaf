"""Block-size table for the fused megakernel tiling: lookup/selection logic.

The sweep callable is injected, so the table behavior is fully testable on
CPU; a lookup without one must NEVER sweep (Engine start-up and kernel
tracing only read the table or take the static default) and must not write
the persisted table.
"""
import json

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.kernels import tune


@pytest.fixture()
def tune_cache(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv("RNS_TUNE_CACHE", str(path))
    tune.clear_memory_cache()
    yield path
    tune.clear_memory_cache()


def test_interpret_fallback_is_static_and_unpersisted(tune_cache):
    b = tune.blocks_for(64, 512, 64, 5)
    assert b == tune.clip_blocks(tune.DEFAULT_BLOCKS, 64, 512, 64)
    assert not tune_cache.exists()            # no table poisoning


def test_sweep_picks_best_and_persists(tune_cache):
    calls = []

    def sweep(blocks):
        calls.append(blocks)
        # favor small bm and small bk — a candidate no static default picks
        return blocks[0] + blocks[2] * 0.1

    best = tune.blocks_for(256, 1024, 256, 5, sweep=sweep)
    assert best == min(calls, key=lambda b: b[0] + b[2] * 0.1)
    assert len(calls) >= 2                    # actually swept
    table = json.loads(tune_cache.read_text())
    assert list(best) in table.values()


def test_table_hit_skips_sweep(tune_cache):
    def sweep(blocks):
        return blocks[0]

    first = tune.blocks_for(128, 512, 128, 5, sweep=sweep)

    def explode(blocks):                      # a second sweep would raise
        raise AssertionError("swept despite table hit")

    again = tune.blocks_for(128, 512, 128, 5, sweep=explode)
    assert again == first
    # the persisted table survives a process restart (simulated by dropping
    # the in-memory cache)
    tune.clear_memory_cache()
    assert tune.blocks_for(128, 512, 128, 5, sweep=explode) == first


def test_cached_entry_clips_to_smaller_shapes(tune_cache):
    tune.blocks_for(256, 1024, 256, 5, sweep=lambda b: 0.0)
    # same key namespace, tiny shape: distinct key → fallback, still clipped
    # (the row extent only down to the int8 sublane tile)
    b = tune.blocks_for(8, 32, 8, 5)
    assert b == (tune.SUBLANE_TILE, 8, 32)


def test_candidates_filtered_by_vmem_budget(tune_cache):
    huge = (4096, 4096, 4096)
    assert tune.vmem_footprint(huge, 6) > tune.VMEM_BUDGET_BYTES
    seen = []

    def sweep(blocks):
        seen.append(blocks)
        return 1.0

    tune.blocks_for(8192, 8192, 8192, 6, sweep=sweep,
                    candidates=[huge, (128, 128, 512)])
    assert all(b != huge for b in seen)


def test_persist_false_leaks_nothing(tune_cache):
    """An experimental (persist=False) sweep must not contaminate the
    shared table — in memory or on disk — via a later persisting call."""
    tune.blocks_for(128, 512, 128, 5, sweep=lambda b: b[0], persist=False)
    assert not tune_cache.exists()
    swept = []
    tune.blocks_for(64, 256, 64, 5, sweep=lambda b: swept.append(b) or 1.0)
    table = json.loads(tune_cache.read_text())
    assert len(table) == 1 and swept  # only the persisting call's entry


def test_corrupt_table_recovers(tune_cache):
    tune_cache.write_text("{not json")
    tune.clear_memory_cache()
    b = tune.blocks_for(64, 512, 64, 5)
    assert b == tune.clip_blocks(tune.DEFAULT_BLOCKS, 64, 512, 64)


def test_fused_kernel_bit_identity_across_tilings(tune_cache):
    """The tuner's freedom is safe: ANY admissible tiling produces the same
    bits (integer stages exact, float epilogue per-element)."""
    from repro.kernels import rns_fused_matmul

    rng = np.random.default_rng(0)
    xq = jnp.asarray(rng.integers(-128, 128, (24, 96)), jnp.int8)
    wq = jnp.asarray(rng.integers(-128, 128, (96, 24)), jnp.int8)
    outs = [np.asarray(rns_fused_matmul(xq, wq, block_m=bm, block_n=bn,
                                        block_k=bk)).tobytes()
            for bm, bn, bk in [(8, 8, 32), (24, 24, 96), (16, 8, 48)]]
    assert outs[0] == outs[1] == outs[2]


# ---------------------------------------------------------------------------
# Zoo prepopulation (serving cold-start: DESIGN.md §15)
# ---------------------------------------------------------------------------


def _no_sweep(monkeypatch):
    def explode(M, K, N, C, backend="pallas_fused"):
        raise AssertionError(f"on-device sweep for M{M}xK{K}xN{N}/C{C} — "
                             "cold start must be table-hit only")
    monkeypatch.setattr(tune, "_default_sweep", explode)


def test_miss_never_sweeps_even_for_native_launches(tune_cache,
                                                     monkeypatch):
    """Start-up/trace contract: a table miss resolves to the static default
    without timing anything — also when the launch compiles natively (the
    resolver used to sweep on device) — and writes no table."""
    _no_sweep(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for M in (1, 4, 8, 256):
        b = tune.blocks_for(M, 576, 1536, 7, backend="pallas_fused_res_emit",
                            x_channels=True, emit=True)
        assert b == tune.clip_blocks(tune.DEFAULT_BLOCKS, M, 576, 1536)
        assert b[0] % tune.SUBLANE_TILE == 0      # never below the tile
    report = tune.warm_for_config(get_smoke_config(
        "rns-smollm-135m-resident"))
    assert report and not any(r["hit"] for r in report)
    assert not tune_cache.exists()


def test_default_table_is_the_committed_file(monkeypatch):
    monkeypatch.delenv("RNS_TUNE_CACHE", raising=False)
    committed = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
                 / "tune_table.json")
    assert tune.cache_path() == committed


@pytest.mark.parametrize("backend", ["pallas_fused", "pallas_fused_res",
                                     "pallas_fused_res_emit"])
def test_default_sweep_times_its_own_variant(backend, monkeypatch):
    """`prepopulate`'s sweep launches the kernel variant the row is keyed
    for (quantize-in / residue-in / residue-emit), in a C-channel basis."""
    from repro.kernels import rns_fused

    launched = []
    real = rns_fused.rns_fused_matmul

    def spy(x, w, *a, **kw):
        launched.append((type(x).__name__, kw.get("quantize", False),
                         kw.get("emit", "float"), len(w.moduli)))
        return real(x, w, *a, **kw)

    monkeypatch.setattr(rns_fused, "rns_fused_matmul", spy)
    t = tune._default_sweep(4, 64, 32, 5, backend)((32, 32, 64), reps=1)
    assert t > 0.0
    kind, quantize, emit, C = launched[0]
    assert C == 5
    assert quantize == (backend == "pallas_fused")
    assert (kind == "RNSTensor") == (backend != "pallas_fused")
    assert emit == ("residues" if backend.endswith("_emit") else "float")


def test_prepopulate_covers_zoo_and_is_idempotent(tune_cache, monkeypatch):
    """`--prepopulate` fills every decode shape of a fused arch (full +
    smoke variants) and a second run writes nothing new."""
    _no_sweep(monkeypatch)          # interpret path must not sweep either
    n = tune.prepopulate(archs=["rns-smollm-135m-resident"])
    assert n > 0
    table = json.loads(tune_cache.read_text())
    assert len(table) == n
    assert tune.prepopulate(archs=["rns-smollm-135m-resident"]) == 0
    # every entry is a concrete admissible tiling for its keyed shape
    for key, blocks in table.items():
        assert len(blocks) == 3 and all(b >= 1 for b in blocks), (key, blocks)


def test_engine_init_zero_sweeps_against_committed_table(monkeypatch):
    """Cold-start contract: with the committed benchmarks/tune_table.json
    every shape `Engine.__init__` warms is a table HIT — no sweeps."""
    import pathlib

    import jax

    from repro.configs.base import get_smoke_config

    if jax.devices()[0].device_kind.replace(" ", "-") != "cpu":
        pytest.skip("committed table is keyed per device kind")
    committed = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
                 / "tune_table.json")
    monkeypatch.setenv("RNS_TUNE_CACHE", str(committed))
    tune.clear_memory_cache()
    try:
        _no_sweep(monkeypatch)
        report = tune.warm_for_config(get_smoke_config(
            "rns-smollm-135m-resident"))
        assert report, "fused config enumerated no decode shapes"
        misses = [r["key"] for r in report if not r["hit"]]
        assert not misses, (
            f"decode shapes missing from committed table: {misses} — "
            "regenerate with `python -m repro.kernels.tune --prepopulate "
            "--out benchmarks/tune_table.json`")
    finally:
        tune.clear_memory_cache()


def test_decode_shapes_cover_real_decode_launches(tune_cache, monkeypatch):
    """`decode_shapes_for` is not a guess: every `blocks_for` lookup a REAL
    decode step performs on the fused-resident config is one of the
    enumerated warm shapes, so a prepopulated table covers decode fully."""
    import jax

    from repro.configs.base import get_smoke_config
    from repro.models import transformer as T
    from repro.serve import Engine

    cfg = get_smoke_config("rns-smollm-135m-resident")
    eng = Engine(cfg, T.make_params(cfg, jax.random.PRNGKey(0)), smax=32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (5, 9)]
    batch, plen = eng._pack(prompts)
    _, cache, pos0 = eng._prefill(eng.params, batch, smax=eng.smax)

    seen = []
    real = tune.blocks_for

    def spy(M, K, N, C, **kw):
        seen.append((kw.get("backend", "pallas_fused"), C, M, K, N,
                     str(kw.get("dtype", "int8"))))
        return real(M, K, N, C, **kw)

    monkeypatch.setattr(tune, "blocks_for", spy)
    step = {"tokens": jnp.zeros((2, 1), jnp.int32)
            if "tokens" in batch else None}
    T.decode_step(cfg, eng.params, cache, step, pos0)
    assert seen, "decode step never consulted the autotuner"
    warm = {(s["backend"], s["C"], s["M"], s["K"], s["N"], s["dtype"])
            for s in tune.decode_shapes_for(cfg)}
    stray = [c for c in seen if c not in warm]
    assert not stray, f"decode launches outside the warmed shape set: {stray}"
