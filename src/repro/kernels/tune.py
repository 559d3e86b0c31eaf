"""Block-size table for the fused RNS megakernel (DESIGN.md §13).

One launch is only a win if it is also a *well-tiled* launch: the fused
kernel holds `C` int32 accumulator planes plus both operand blocks in VMEM,
so the best (bm, bn, bk) depends on the channel count and the shape in a way
a single static default cannot cover.  `blocks_for` resolves the tiling from
exactly two sources, and compiles or times nothing:

  1. a JSON table keyed by (variant, device kind, dtype, C, M, K, N) — by
     default the committed ``benchmarks/tune_table.json`` of this checkout
     (``$RNS_TUNE_CACHE`` points elsewhere);
  2. on a miss, the static default `DEFAULT_BLOCKS`, clipped to the shape
     (`clip_blocks`) — VMEM-admissible and compiled on the chip.

Sweeping is an offline step of its own: ``python -m repro.kernels.tune
--prepopulate`` times each missing decode shape of the config zoo on the
device it runs on and writes the winners to the table.  Engine start-up and
kernel tracing never sweep, so a cold start compiles only what it serves.

Bit-identity does not depend on the tiling (the integer stages are exact and
the float epilogue runs per output element), so any admissible entry is
safe — it changes *when* blocks are scheduled, never what they compute.

The sweep callable is injectable (``sweep=``) so the selection logic is
unit-testable off-TPU (`tests/test_tune.py`).
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Callable, Optional, Sequence, Tuple

__all__ = ["DEFAULT_BLOCKS", "CANDIDATES", "DECODE_CANDIDATES", "SUBLANE_TILE",
           "blocks_for", "clip_blocks", "cache_path", "clear_memory_cache",
           "vmem_footprint",
           "decode_shapes_for", "warm_for_config", "prepopulate",
           "shape_key", "parse_shape_key"]

Blocks = Tuple[int, int, int]

# Static fallback — the staged kernel's proven default tiling.
DEFAULT_BLOCKS: Blocks = (128, 128, 512)

# Sweep candidates: MXU-aligned (multiples of the 128-lane tile; bk a
# multiple of 256 keeps int8 sublane packing happy) spanning the
# square/tall/wide/deep-K corners of the space.
CANDIDATES: Tuple[Blocks, ...] = (
    (128, 128, 512),
    (128, 128, 1024),
    (128, 256, 512),
    (256, 128, 512),
    (256, 256, 256),
    (128, 128, 256),
    (64, 128, 512),
    (128, 64, 512),
)

# Decode-shape candidates: M is the batch size (a handful of rows per token
# step), so a 128-row bm pads 4–32× dead sublanes per tile.  bm stays at the
# int8 sublane tile or one above it (M itself is padded up to the tile,
# `clip_blocks`), while bn/bk stream MXU-aligned blocks — `blocks_for`
# switches to this pool automatically for M ≤ 64.
DECODE_CANDIDATES: Tuple[Blocks, ...] = (
    (32, 128, 512),
    (32, 256, 512),
    (32, 128, 1024),
    (32, 256, 256),
    (32, 512, 512),
    (64, 128, 512),
    (64, 256, 256),
    (64, 256, 512),
)

# Row tile every block's M extent is padded up to: int8 operands pack 32
# rows per (32, 128) vreg tile, which also covers the f32/int32 (8, 128)
# tiles of the scale, accumulator and output blocks.
SUBLANE_TILE = 32

# VMEM budget the candidate filter admits against (per-core VMEM is ~16 MiB;
# leave headroom for double buffering of the streamed operands).
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

_MEMORY_CACHE: dict = {}


def cache_path() -> Path:
    """The tuning table: ``$RNS_TUNE_CACHE`` or, by default, the committed
    ``benchmarks/tune_table.json`` of this checkout."""
    env = os.environ.get("RNS_TUNE_CACHE")
    if env:
        return Path(env).expanduser()
    return Path(__file__).resolve().parents[3] / "benchmarks" / "tune_table.json"


def clear_memory_cache() -> None:
    """Drop the in-process table (tests re-point RNS_TUNE_CACHE)."""
    _MEMORY_CACHE.clear()


def vmem_footprint(blocks: Blocks, C: int, *, itemsize: int = 1,
                   encoded: bool = True, x_channels: bool = False,
                   emit: bool = False) -> int:
    """Approximate per-step VMEM bytes of the fused kernel at this tiling:
    activation block(s) + weight block(s) + the (C, bm, bn) int32 accumulator
    scratch + the output tile.  ``x_channels`` sizes a residue-in activation
    (the (C, bm, bk) stack of a chained launch); ``emit`` sizes the
    (C, bm, bn) residue output tile instead of the f32 one."""
    bm, bn, bk = blocks
    w_blocks = C if encoded else 1
    x_blocks = C if x_channels else 1
    out_bytes = C * bm * bn * itemsize if emit else bm * bn * 4
    return (x_blocks * bm * bk * itemsize + w_blocks * bk * bn * itemsize
            + C * bm * bn * 4 + out_bytes)


def clip_blocks(blocks: Blocks, M: int, K: int, N: int) -> Blocks:
    """Clip a tiling to a launch shape.  bn/bk clip to N/K (a block equal to
    the full dimension is always legal); bm clips to M rounded UP to the
    `SUBLANE_TILE`, never below it — the kernel pads M to the block, so a
    decode launch of a few rows runs one tile-aligned row block instead of
    an int8 block whose sublane extent is below the native tile."""
    bm, bn, bk = blocks
    m_tile = -(-M // SUBLANE_TILE) * SUBLANE_TILE
    return (min(bm, m_tile), min(bn, N), min(bk, K))


def _load_table() -> dict:
    path = cache_path()
    key = str(path)
    if key not in _MEMORY_CACHE:
        table = {}
        try:
            table = json.loads(path.read_text())
            if not isinstance(table, dict):
                table = {}
        except (OSError, ValueError):
            table = {}
        _MEMORY_CACHE[key] = table
    return _MEMORY_CACHE[key]


def _save_table(table: dict) -> None:
    path = cache_path()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(table, indent=1, sort_keys=True))
    except OSError:
        pass                     # read-only FS: keep the in-memory table


def _shape_key(M: int, K: int, N: int, C: int, dtype: str,
               backend: str) -> str:
    import jax

    # device_kind, not the platform string: a table swept on one TPU
    # generation must not be a key hit on another (different VMEM/MXU).
    kind = jax.devices()[0].device_kind.replace(" ", "-")
    return f"{backend}/{kind}/{dtype}/C{C}/M{M}xK{K}xN{N}"


def shape_key(M: int, K: int, N: int, C: int, dtype: str = "int8",
              backend: str = "pallas_fused") -> str:
    """The table key `blocks_for` looks up for this launch (public form)."""
    return _shape_key(M, K, N, C, dtype, backend)


def parse_shape_key(key: str) -> dict:
    """Invert the table-key format ``backend/device/dtype/C{C}/M{M}xK{K}xN{N}``.

    Returns ``{backend, device, dtype, C, M, K, N, x_channels, emit}`` —
    the variant flags are decoded from the backend suffix (`_res` streams a
    (C, bm, bk) residue activation, `_emit` writes the (C, bm, bn) residue
    output tile), which is what sizes the VMEM admissibility filter.
    Raises ``ValueError`` naming the malformed segment.
    """
    parts = key.split("/")
    if len(parts) != 5:
        raise ValueError(f"tune-table key {key!r}: expected 5 segments "
                         f"backend/device/dtype/C.../M...xK...xN..., "
                         f"got {len(parts)}")
    backend, device, dtype, c_part, shape_part = parts
    if not c_part.startswith("C") or not c_part[1:].isdigit():
        raise ValueError(f"tune-table key {key!r}: channel segment "
                         f"{c_part!r} is not of the form C<int>")
    import re

    m = re.fullmatch(r"M(\d+)xK(\d+)xN(\d+)", shape_part)
    if m is None:
        raise ValueError(f"tune-table key {key!r}: shape segment "
                         f"{shape_part!r} is not of the form M<i>xK<i>xN<i>")
    return {
        "backend": backend, "device": device, "dtype": dtype,
        "C": int(c_part[1:]),
        "M": int(m.group(1)), "K": int(m.group(2)), "N": int(m.group(3)),
        "x_channels": "_res" in backend,
        "emit": "_emit" in backend,
    }


def _default_sweep(M: int, K: int, N: int, C: int,
                   backend: str = "pallas_fused") -> Callable[[Blocks], float]:
    """Time the kernel VARIANT a table row is keyed for, on synthetic
    operands in a C-channel basis: ``pallas_fused`` is the quantize-in,
    encoded-weight launch of a float-domain linear; ``pallas_fused_res``
    the residue-in launch of a chain; ``pallas_fused_res_emit`` the same
    with the in-domain requantize epilogue.  Only `prepopulate` calls this,
    on the device it is tuning."""
    import time

    import jax
    import numpy as np
    import jax.numpy as jnp

    from repro.core.rns import PAPER_N5_MODULI, RNSBasis
    from repro.core.rns_tensor import encode, encode_activation
    from .rns_fused import rns_fused_matmul

    if backend not in ("pallas_fused", "pallas_fused_res",
                       "pallas_fused_res_emit"):
        raise ValueError(f"no sweep for kernel variant {backend!r}")
    # the C largest odd paper moduli: the basis every sizing rule
    # (`basis_for_int8_matmul`, `basis_for_chain`) picks for C channels
    odd = sorted((m for m in PAPER_N5_MODULI if m != 1024), reverse=True)
    basis = RNSBasis(name=f"sweep-C{C}", moduli=tuple(odd[:C]))
    rng = np.random.default_rng(0)
    xf = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = encode(jnp.asarray(rng.standard_normal((K, N)), jnp.float32), basis)
    if backend == "pallas_fused":
        x = xf
        kw = dict(quantize=True, scale_row=jnp.max(jnp.abs(xf), axis=-1,
                                                   keepdims=True) / 127.0)
    else:
        x = encode_activation(xf, basis)
        kw = dict(emit="residues" if backend.endswith("_emit") else "float")

    def run(blocks: Blocks, reps: int = 3) -> float:
        bm, bn, bk = blocks
        fn = jax.jit(lambda a, b: rns_fused_matmul(
            a, b, scale_col=b.scale, block_m=bm, block_n=bn, block_k=bk,
            **kw))
        jax.block_until_ready(fn(x, w))              # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, w))
            best = min(best, time.perf_counter() - t0)
        return best

    return run


def blocks_for(M: int, K: int, N: int, C: int, *, dtype: str = "int8",
               backend: str = "pallas_fused",
               x_channels: bool = False, emit: bool = False,
               sweep: Optional[Callable[[Blocks], float]] = None,
               candidates: Optional[Sequence[Blocks]] = None,
               persist: bool = True) -> Blocks:
    """Resolve (block_m, block_n, block_k) for one fused-kernel shape.

    Table hit → the stored choice; miss → the static default.  Both are
    clipped to the shape (`clip_blocks`), and nothing is compiled or timed.
    Only an injected ``sweep`` (`prepopulate`, tests) times the
    VMEM-admissible candidates on a miss and persists the winner
    (``persist=False`` keeps it out of every table).  ``backend`` names the
    kernel *variant* ("pallas_fused", "pallas_fused_res",
    "pallas_fused_res_emit", …) so residue-in/emit launches own their table
    rows; ``x_channels``/``emit`` size the VMEM filter for them.  Decode
    shapes (M ≤ 64) sweep `DECODE_CANDIDATES` by default.
    """
    table = _load_table()
    key = _shape_key(M, K, N, C, dtype, backend)
    hit = table.get(key)
    if hit is not None:
        return clip_blocks(tuple(int(v) for v in hit), M, K, N)
    if sweep is None:
        return clip_blocks(DEFAULT_BLOCKS, M, K, N)

    if candidates is None:
        candidates = DECODE_CANDIDATES if M <= 64 else CANDIDATES
    pool = [tuple(c) for c in candidates
            if vmem_footprint(tuple(c), C, x_channels=x_channels,
                              emit=emit) <= VMEM_BUDGET_BYTES]
    if not pool:
        pool = [DEFAULT_BLOCKS]
    # Clipping collapses candidates at small shapes — sweep distinct ones.
    seen, distinct = set(), []
    for c in pool:
        cl = clip_blocks(c, M, K, N)
        if cl not in seen:
            seen.add(cl)
            distinct.append(cl)
    best = min(distinct, key=sweep)
    if persist:
        # persist=False leaves BOTH tables untouched — an experimental
        # sweep must not leak into the shared in-memory dict, where a later
        # persisting call would flush it to disk as a tuned-on-device hit.
        table[key] = list(best)
        _save_table(table)
    return best


# -------------------------------------------------- serving prepopulation --
# Decode batch sizes the config zoo's serving paths launch: the static
# engine decodes at the generate() batch size, the slot scheduler at its
# (fixed) slot count — both a handful of rows.
ZOO_BATCH_SIZES = (1, 2, 4, 8)


def decode_shapes_for(cfg, batch_sizes=ZOO_BATCH_SIZES):
    """Enumerate the fused-megakernel launch shapes of ONE decode step.

    Mirrors the dispatch in models/{transformer,layers}.py: per-linear
    launches for ``domain="float"``; the stacked-QKV chain, residue-resident
    GLU chain (gate / up-with-emit / gated-down) and the plain wo launch for
    ``domain="residue"`` (DESIGN.md §14).  Returns a deduped list of dicts
    ``{backend, C, M, K, N, dtype, x_channels, emit}`` — empty for configs
    that never hit the fused kernel.
    """
    spec = cfg.linear_spec
    if not (spec.is_rns and spec.backend == "pallas_fused"):
        return []
    from repro.core.channel_plan import residue_dtype_for
    from repro.core.rns import basis_for_chain, basis_for_int8_matmul

    d, F = cfg.d_model, cfg.d_ff
    H, Hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    has_attn = cfg.attention != "none" or cfg.hybrid
    shapes, seen = [], set()

    def add(backend, basis, M, K, N, x_channels=False, emit=False):
        import jax.numpy as jnp
        dtype = str(jnp.dtype(residue_dtype_for(basis.moduli)))
        s = (backend, len(basis.moduli), M, K, N, dtype, x_channels, emit)
        if s not in seen:
            seen.add(s)
            shapes.append(dict(backend=backend, C=len(basis.moduli), M=M,
                               K=K, N=N, dtype=dtype, x_channels=x_channels,
                               emit=emit))

    for M in batch_sizes:
        if spec.domain == "residue":
            if has_attn:
                # stacked-QKV chain launch + the plain wo exit launch
                add("pallas_fused_res", basis_for_int8_matmul(d), M, d,
                    (H + 2 * Hk) * dh, x_channels=True)
                add("pallas_fused", basis_for_int8_matmul(H * dh), M,
                    H * dh, d)
            if cfg.glu and F > 0:
                cb = basis_for_chain(F)
                add("pallas_fused_res", cb, M, d, F, x_channels=True)
                add("pallas_fused_res_emit", cb, M, d, F, x_channels=True,
                    emit=True)
                add("pallas_fused_res", cb, M, F, d, x_channels=True)
        else:
            pairs = set()
            if has_attn:
                pairs |= {(d, H * dh), (d, Hk * dh), (H * dh, d)}
            if F > 0:
                pairs |= {(d, F), (F, d)}
            for K, N in sorted(pairs):
                add("pallas_fused", basis_for_int8_matmul(K), M, K, N)
    return shapes


def warm_for_config(cfg, batch_sizes=ZOO_BATCH_SIZES):
    """Resolve every decode shape of ``cfg`` through `blocks_for` (called by
    `serve.Engine.__init__`) and report which are table hits: a miss serves
    the static default.  Compiles and times nothing.  Returns
    ``[{key, hit, blocks}, …]`` (empty for non-fused configs)."""
    report = []
    table = _load_table()
    for s in decode_shapes_for(cfg, batch_sizes):
        key = _shape_key(s["M"], s["K"], s["N"], s["C"], s["dtype"],
                         s["backend"])
        blocks = blocks_for(s["M"], s["K"], s["N"], s["C"], dtype=s["dtype"],
                            backend=s["backend"], x_channels=s["x_channels"],
                            emit=s["emit"])
        report.append({"key": key, "hit": key in table,
                       "blocks": tuple(blocks)})
    return report


def _fused_archs():
    from repro.configs.base import get_config, list_archs

    return [name for name in list_archs()
            if get_config(name).linear_spec.backend == "pallas_fused"]


def prepopulate(archs=None, batch_sizes=ZOO_BATCH_SIZES) -> int:
    """Offline table prepopulation for the config zoo's decode shapes
    (``python -m repro.kernels.tune --prepopulate``) — the ONE place that
    sweeps.

    On a TPU: a best-of-reps sweep of each missing shape's own kernel
    variant (`_default_sweep`).  Under interpret (CPU): the clipped static
    default is written EXPLICITLY — interpret timings measure the Python
    grid loop, not hardware, but a committed entry still makes cold-start
    lookups hits (the key carries the device kind, so a TPU run fills its
    own rows).  Covers both the full and the smoke variant of every
    fused-backend arch; returns the number of NEW entries written.
    """
    from repro.configs.base import get_config, get_smoke_config
    from repro.core.channel_plan import resolve_interpret

    names = list(archs) if archs is not None else _fused_archs()
    cfgs = []
    for name in names:
        cfgs.append(get_config(name))
        cfgs.append(get_smoke_config(name))
    table = _load_table()
    new = 0
    for cfg in cfgs:
        for s in decode_shapes_for(cfg, batch_sizes):
            key = _shape_key(s["M"], s["K"], s["N"], s["C"], s["dtype"],
                             s["backend"])
            if key in table:
                continue
            if resolve_interpret(None):
                table[key] = list(clip_blocks(DEFAULT_BLOCKS, s["M"], s["K"],
                                              s["N"]))
            else:
                blocks_for(s["M"], s["K"], s["N"], s["C"], dtype=s["dtype"],
                           backend=s["backend"], x_channels=s["x_channels"],
                           emit=s["emit"],
                           sweep=_default_sweep(s["M"], s["K"], s["N"],
                                                s["C"], s["backend"]))
            new += 1
    _save_table(table)
    return new


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Autotuner table maintenance for the fused megakernel")
    ap.add_argument("--prepopulate", action="store_true",
                    help="fill the table for the config zoo's decode shapes "
                         "(device: swept; interpret: static defaults)")
    ap.add_argument("--out", default=None,
                    help="table path (defaults to $RNS_TUNE_CACHE / the "
                         "committed benchmarks/tune_table.json)")
    ap.add_argument("--archs", default=None,
                    help="comma-separated arch names (default: every "
                         "fused-backend arch in the registry)")
    args = ap.parse_args(argv)
    if args.out:
        os.environ["RNS_TUNE_CACHE"] = args.out
        clear_memory_cache()
    if args.prepopulate:
        archs = args.archs.split(",") if args.archs else None
        n = prepopulate(archs=archs)
        print(f"# prepopulate: {n} new entries -> {cache_path()} "
              f"({len(_load_table())} total)")
        return 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    import sys

    sys.exit(_main())
