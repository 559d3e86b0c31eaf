"""Block/paged KV cache for continuous-batching serving (DESIGN.md §15).

The static engine reserves ``B·smax`` K/V rows per layer — every slot pays
for the longest request it might ever hold.  Here the K/V storage is a pool
of fixed-size *physical blocks* shared by all slots; a host-side block table
maps ``(slot, logical block) → physical block`` and peak cache HBM is set by
the aggregate *live* tokens, not the reservation.  Three pieces:

  * :class:`BlockAllocator` — host-side free list + refcounts + an exact
    token-prefix registry (prefix caching): a full block whose content is a
    prompt prefix can be mapped by several requests at once (copy-on-write
    by construction — decode only ever writes a slot's own *private* tail
    and decode blocks, never a shared full block);
  * :func:`init_paged_cache` — the device pool pytree, mirroring
    `models.transformer.init_cache` leaf structure except that the
    attention K/V leaves are one pool ``kv: (n_blocks_layers, n_phys,
    block, 2·Hk·dh)``, a token's K then its V in one row (DESIGN.md §15).
    Physical block 0 is reserved as the *trash* block: idle slots and
    out-of-range writes land there and it is never read unmasked.  SSM
    state/conv stay slot-resident (they are O(1) per slot — there is
    nothing to page);
  * :func:`splice_prefill` — one jitted scatter that copies a freshly
    prefilled B=1 cache into the pool blocks (and the SSM slot row) of an
    admitted request.

Ring (SWA) caches are rejected at pool construction: their ``cache_pos`` is
a single (W,) vector shared across the batch, which cannot represent
per-slot write positions (DESIGN.md §15 records the scope).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dtype_of
from repro.models.ssm import init_ssm_cache
from repro.models.transformer import FULL_WINDOW, _kv_rows, _mixer_kind

__all__ = ["BlockAllocator", "init_paged_cache", "splice_prefill",
           "paged_cache_nbytes"]


class BlockAllocator:
    """Host-side physical-block bookkeeping: free list, refcounts, and the
    exact-prefix registry for shared prompt-head blocks.

    Prefix keys are the *exact* token tuple of the prompt head the block
    completes (content-addressed — no hash-collision aliasing).  Only full
    blocks register; a block is freed (and deregistered) when its refcount
    drops to zero, so a cached prefix lives as long as some holder does.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the "
                             "reserved trash block)")
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        self._by_prefix: Dict[Tuple[int, ...], int] = {}
        self._prefix_of: Dict[int, Tuple[int, ...]] = {}
        self.peak_used = 0
        self.prefix_hits = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used(self) -> int:
        return (self.n_blocks - 1) - len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("paged KV pool exhausted — size n_blocks to "
                               "the admission-time reservation bound")
        b = self._free.pop()
        self._refs[b] = 1
        self.peak_used = max(self.peak_used, self.used)
        return b

    def retain(self, b: int) -> None:
        self._refs[b] += 1

    def release(self, b: int) -> None:
        self._refs[b] -= 1
        if self._refs[b] == 0:
            del self._refs[b]
            pfx = self._prefix_of.pop(b, None)
            if pfx is not None:
                del self._by_prefix[pfx]
            self._free.append(b)

    def lookup(self, prefix: Tuple[int, ...]) -> Optional[int]:
        return self._by_prefix.get(prefix)

    def register(self, prefix: Tuple[int, ...], b: int) -> None:
        self._by_prefix[prefix] = b
        self._prefix_of[b] = prefix


def init_paged_cache(cfg: ModelConfig, n_phys: int, block_size: int,
                     slots: int):
    """Zeroed paged decode cache: pooled K/V + slot-resident SSM state.

    Leaf structure mirrors `transformer.init_cache` (``sub{i}`` columns
    stacked over blocks); the attention leaves ``k``/``v`` become one pool
    ``kv: (n_blocks_layers, n_phys, block_size, 2·Hk·dh)`` instead of
    ``(…, B, smax, …)`` reservations.  One row holds a token's K then its
    V, so a row is a whole number of the chip's 128 lanes (smollm-135m:
    2 × 3 × 64 = 384) and the TPU keeps the pool as rows, untransposed and
    unpadded: the decode step then gathers and scatters whole rows with no
    relayout of the pool (DESIGN.md §15).
    """
    dtype = dtype_of(cfg)
    Hk, dh = cfg.num_kv_heads, cfg.head_dim
    kind = _mixer_kind(cfg)
    L = cfg.n_blocks
    out = {}
    for i in range(cfg.layers_per_block):
        col = {}
        if kind in ("attn", "hybrid"):
            for b in range(L):
                layer = b * cfg.layers_per_block + i
                if cfg.window_for_layer(layer, FULL_WINDOW) < FULL_WINDOW:
                    raise ValueError(
                        f"{cfg.name}: layer {layer} uses a sliding-window "
                        "ring cache — paged decode supports full-attention "
                        "and pure-SSM stacks only (DESIGN.md §15)")
            # the stacked pool in one allocation: per-layer zeros and a
            # stack would hold the pool two and three times over
            col["kv"] = jnp.zeros((L, n_phys, block_size, 2 * Hk * dh), dtype)
        if kind in ("ssm", "hybrid"):
            col["ssm"] = jax.tree.map(
                lambda x: jnp.zeros((L, *x.shape), x.dtype),
                jax.eval_shape(lambda: init_ssm_cache(cfg, slots, dtype)))
        out[f"sub{i}"] = col
    return out


def paged_cache_nbytes(cache) -> int:
    """Actual device bytes of the pool pytree (the honest peak-HBM figure
    `benchmarks/serving_bench.py` reports against B·smax)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache))


@functools.partial(jax.jit, donate_argnums=(0,))
def splice_prefill(cache, pf_cache, slot, phys, offs):
    """Copy an admitted request's B=1 prefill cache into the pool.

    ``phys``/``offs`` ((S,) int32, host-built) give the (physical block,
    offset) destination of each *padded* prefill position; pad slots and
    positions landing in SHARED prefix blocks are routed to the trash block
    (phys 0) — shared blocks are read-only by construction and already hold
    bit-identical K/V (causality: a prefix position's K/V depends only on
    prefix tokens).  SSM leaves copy into the slot's batch row.

    One jitted executable per (prefill-bucket, cache-structure) shape; the
    pool is donated, so the splice updates in place.
    """
    out = {}
    for sub, col in cache.items():
        new = dict(col)
        if "kv" in col:
            # as rows (L, n_phys·block, 2·Hk·dh), indexed like the decode
            # step's write, so the scatter updates the pool in place
            L, _, block, width = col["kv"].shape
            layers = jnp.arange(L, dtype=jnp.int32)[:, None]
            new["kv"] = col["kv"].reshape(L, -1, width).at[
                layers, (phys * block + offs)[None]].set(
                _kv_rows(pf_cache[sub]["k"][:, 0], pf_cache[sub]["v"][:, 0])
            ).reshape(col["kv"].shape)
        if "ssm" in col:
            new["ssm"] = jax.tree.map(
                lambda dst, src: dst.at[:, slot].set(src[:, 0]),
                col["ssm"], pf_cache[sub]["ssm"])
        out[sub] = new
    return out
