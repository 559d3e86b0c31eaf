"""Continuous-batching slot scheduler over the paged KV pool (DESIGN.md §15).

The static `serve.Engine` packs one batch, runs it to completion, and every
request waits for the slowest batchmate while ``B·smax`` KV rows stay
reserved.  :class:`SlotScheduler` instead keeps a fixed set of decode
*slots* hot and admits requests from an arrival queue the moment a slot
frees up mid-flight:

  * the decode hot path stays ONE jitted executable — the slot axis has a
    fixed size ``slots``, idle slots ride along with ``done=True`` and all
    their writes routed to the pool's trash block, so admission/retirement
    never changes a traced shape;
  * K/V lives in the paged pool (`serve.paged_cache`): admission reserves a
    request's whole-lifetime block budget up front (no mid-flight
    exhaustion, by construction), retirement frees the blocks for the next
    request, and prompt-head blocks shared with earlier requests are
    refcount-mapped instead of copied (prefix caching; shared blocks are
    never written — copy-on-write by construction, see paged_cache);
  * prefill happens on admission through the STATIC engine's own jitted
    prefill at `bucket_plen`-bucketed lengths, then is spliced into the
    pool — so the scheduler reuses the engine's weight encoding, tuned
    megakernel table, and compile caches.

Greedy outputs are bit-identical to ``Engine.generate([prompt])`` run alone
with ``smax == slot_tokens``: splicing strips the pad (the pool is indexed
by *logical* position), decode attends over a view that puts every key back
in the slot the solo engine's left-padded cache holds it in (so the key-axis
reductions group identically on an accelerator, not only on a sequential
CPU), masked gather rows contribute exact float zeros (DESIGN.md §11), and
equal key-axis lengths keep the reduction shapes identical.  The first token of every request is sampled with exactly the
solo engine's key chain (``split(PRNGKey(seed))``), so bit-identity holds
regardless of when the request is admitted or who its slot-mates are —
`tests/test_scheduler.py` asserts arrival-order invariance for float and
residue-domain configs.

Tracing: ``serve()`` writes host spans with `jax.profiler.TraceAnnotation`,
which cost well under a microsecond each with no profiler running and land
in the same trace as the device's programs when one is: ``serve.call``
around the whole call, ``serve.admit`` (``rid=`` the request's input index)
around each seated admission, ``serve.chunk`` around each decode chunk, and
inside those two ``serve.upload`` (host→device puts) and ``serve.readback``
(host reads that wait for the device).  ``stats["per_request"]`` gives each
request's wall-clock stamps from ``serve()`` entry (PERF.md §3).

Scope: full-attention and pure-SSM stacks.  Sliding-window layers keep a
ring cache whose write cursor is shared across the batch; those
architectures are rejected at construction and served by the static engine
(DESIGN.md §15 records the scope decision).
"""
from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.serve.engine import Engine, _sample_traced, bucket_plen
from repro.serve.paged_cache import (BlockAllocator, init_paged_cache,
                                     paged_cache_nbytes, splice_prefill)

__all__ = ["Request", "SlotScheduler"]


@dataclass
class Request:
    """One serving request.  ``arrival`` is in virtual decode steps (the
    scheduler's clock advances ``decode_chunk`` per chunk); ``seed`` is the
    request's own sampling chain — the solo-engine call it must match
    bit-for-bit is ``Engine.generate([prompt], max_new_tokens, seed=seed)``."""
    prompt: List[int]
    max_new_tokens: int = 32
    seed: int = 0
    arrival: float = 0.0
    rid: Optional[int] = None


@dataclass
class _Slot:
    req: Request
    blocks: List[int]            # physical blocks held (shared ones retained)
    out: List[int]               # emitted new tokens (first included)
    finished: bool = False
    # wall clock, seconds from serve() entry
    seated_s: float = 0.0        # blocks reserved, admission begins
    first_s: float = 0.0         # first token on the host
    last_s: float = 0.0          # last token on the host


def _sample_rows(logits, temperature, keys):
    """Per-row sampling for the slot batch: greedy at t ≤ 0 (the bit-identity
    criterion), per-slot categorical chains at t > 0."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.asarray(temperature, jnp.float32)
    scaled = logits / jnp.where(t > 0.0, t, 1.0)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row))(keys, scaled)
    return jnp.where(t > 0.0, sampled.astype(jnp.int32), greedy)


def chunk_program(cfg: ModelConfig, chunk: int):
    """The jitted decode chunk: ``chunk`` paged decode steps with on-device
    sampling, over the slot state and the donated K/V pool."""

    def run(params, cache, bt, shift, cur, done, pos, keys, temperature, eos):
        def step(carry, _):
            cur, done, cache, pos, keys = carry
            logits, cache = T.decode_step(
                cfg, params, cache, {"tokens": cur[:, None]}, pos + shift,
                positions=pos, block_tables=bt)
            ks = jax.vmap(jax.random.split)(keys)
            nxt = _sample_rows(logits, temperature, ks[:, 1])
            new_done = done | (nxt == eos)
            # freeze a finished lane's position: its junk steps keep
            # overwriting ONE private row instead of marching into
            # unmapped (trash-routed) territory; either way nothing
            # emitted past `done` is read.
            pos = jnp.where(new_done, pos, pos + 1)
            return (nxt, new_done, cache, pos, ks[:, 0]), (nxt, ~done)

        (cur, done, cache, pos, keys), (toks, emit) = jax.lax.scan(
            step, (cur, done, cache, pos, keys), None, length=chunk)
        return cur, done, cache, pos, keys, toks, emit

    # the pool is donated: the scheduler rebinds it every chunk, so XLA
    # updates the K/V blocks in place instead of copying the pool.
    return jax.jit(run, donate_argnums=(1,))


class SlotScheduler:
    """Continuous-batching scheduler: ``slots`` resident decode lanes over a
    paged KV pool of ``n_blocks × block_size`` token rows.

    ``slot_tokens`` is each lane's logical capacity (and the ``smax`` of the
    internal static engine — keep them equal for bit-identity comparisons);
    ``n_blocks`` sizes the PHYSICAL pool, normally far below the static
    reservation ``slots · slot_tokens / block_size`` — peak KV HBM is set by
    aggregate live tokens, not by lanes × max length.

    Admission is strict arrival order (no head-of-line bypass: determinism
    and the arrival-order-invariance contract come first), at chunk
    boundaries — ``decode_chunk=1`` gives per-step admission.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 block_size: int = 16, slot_tokens: int = 256,
                 n_blocks: Optional[int] = None, decode_chunk: int = 8,
                 temperature: float = 0.0, eos_id: Optional[int] = None,
                 prefix_sharing: bool = True, mesh=None,
                 dist_layout: Optional[str] = None):
        if slot_tokens % block_size:
            raise ValueError("slot_tokens must be a multiple of block_size")
        self.cfg = cfg
        self.slots = int(slots)
        self.block_size = int(block_size)
        self.slot_tokens = int(slot_tokens)
        self.nlog = slot_tokens // block_size
        self.n_blocks = int(n_blocks) if n_blocks is not None \
            else 1 + self.slots * self.nlog
        self.decode_chunk = int(decode_chunk)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.prefix_sharing = bool(prefix_sharing)
        # the engine owns weight encoding, jitted prefill, and the tune warm.
        # lanes=slots pins the engine's decode batch width to the slot
        # count: XLA reduction order is shape-dependent, so the solo bit-
        # reference must run the same (slots, …) shapes as the chunk fn.
        self.engine = Engine(cfg, params, smax=slot_tokens, lanes=self.slots,
                             mesh=mesh, dist_layout=dist_layout)
        # fail fast on ring-cache architectures (and validate pool shapes)
        init_paged_cache(cfg, 2, block_size, 1)
        self._chunk_fn = chunk_program(cfg, self.decode_chunk)

        # admission-time first token, one dispatch: the solo engine's exact
        # chain — split(PRNGKey(seed)), sample with the sub-key, carry the
        # rest (eager jax.random ops cost milliseconds per admission on CPU)
        def _first(logits, temperature, seed):
            key, k0 = jax.random.split(jax.random.PRNGKey(seed))
            return key, _sample_traced(logits, temperature, k0)

        self._first_fn = jax.jit(_first)
        self.stats: Dict[str, Any] = {}

    # ------------------------------------------------------------- admission -
    def _lifetime_blocks(self, req: Request) -> int:
        return -(-(len(req.prompt) + req.max_new_tokens) // self.block_size)

    def _try_admit(self, req: Request, slot: int,
                   rid: int) -> Optional[_Slot]:
        """Reserve blocks, prefill, splice, and seat ``req`` (input index
        ``rid``) in ``slot``.  Returns None (state untouched, no span) when
        the pool cannot cover the request's whole-lifetime reservation
        yet."""
        bs, prompt = self.block_size, req.prompt
        plen = len(prompt)
        nfull = plen // bs
        shared: List[int] = []
        if self.prefix_sharing:
            for j in range(nfull):
                b = self._alloc.lookup(tuple(prompt[:(j + 1) * bs]))
                if b is None:
                    break
                shared.append(b)
        lifetime = self._lifetime_blocks(req)
        if self._alloc.free_count < lifetime - len(shared):
            return None
        with TraceAnnotation("serve.admit", rid=rid):
            seated_s = time.perf_counter() - self._t0
            self._alloc.prefix_hits += len(shared)
            for b in shared:
                self._alloc.retain(b)
            blocks = shared + [self._alloc.alloc()
                               for _ in range(lifetime - len(shared))]
            self._bt[slot, :] = -1
            self._bt[slot, :lifetime] = blocks

            # prefill alone at the bucketed length (the solo engine's own
            # packed shape — identical pad, hence bit-identical K/V), then
            # splice.
            with TraceAnnotation("serve.upload", rid=rid):
                batch, _ = self.engine._pack([prompt])
            lanes, pbuck = batch["tokens"].shape
            self._prefill_rows += lanes * pbuck
            pad = pbuck - plen
            # decode attends over a view that holds logical position r at
            # slot r + pad — the solo engine's left-padded cache layout, so
            # the key axis reduces in the same order — wherever that layout
            # fits the slot (the solo reference itself needs pbuck + new ≤
            # smax)
            self._shift[slot] = (pad if pbuck + req.max_new_tokens
                                 <= self.slot_tokens else 0)
            with self.engine._ctx():
                logits, pf_cache, _ = self.engine._prefill(
                    self.engine.params, batch, smax=pbuck)
            phys = np.zeros((pbuck,), np.int32)
            offs = np.zeros((pbuck,), np.int32)
            for s in range(pbuck):
                lp = s - pad
                if lp < 0 or lp // bs < len(shared):
                    continue    # pad slots / already-shared blocks → trash
                phys[s] = blocks[lp // bs]
                offs[s] = lp % bs
            with TraceAnnotation("serve.upload", rid=rid):
                slot_d, phys_d, offs_d = (jnp.int32(slot),
                                          jnp.asarray(phys),
                                          jnp.asarray(offs))
            self._cache = splice_prefill(self._cache, pf_cache, slot_d,
                                         phys_d, offs_d)
            if self.prefix_sharing:
                for j in range(len(shared), nfull):
                    self._alloc.register(tuple(prompt[:(j + 1) * bs]),
                                         blocks[j])

            with TraceAnnotation("serve.upload", rid=rid):
                temp_d, seed_d = (jnp.float32(self.temperature),
                                  jnp.int32(req.seed))
            key, first_arr = self._first_fn(logits, temp_d, seed_d)
            with TraceAnnotation("serve.readback", rid=rid):
                first = int(first_arr[0])
            st = _Slot(req=req, blocks=blocks, out=[first], seated_s=seated_s,
                       first_s=time.perf_counter() - self._t0)
            if req.max_new_tokens <= 1 or (self.eos_id is not None
                                           and first == self.eos_id):
                st.finished, st.last_s = True, st.first_s
                self._release(slot, st)
            else:
                self._slots[slot] = st
                self._cur[slot] = first
                self._pos[slot] = plen
                self._done[slot] = False
                with TraceAnnotation("serve.readback", rid=rid):
                    self._keys[slot] = key
            return st

    def _release(self, slot: int, st: _Slot) -> None:
        for b in st.blocks:
            self._alloc.release(b)
        self._bt[slot, :] = -1
        self._shift[slot] = 0
        self._done[slot] = True
        self._slots[slot] = None

    # ----------------------------------------------------------------- serve -
    def serve(self, requests: Sequence[Request]) -> List[List[int]]:
        """Run every request to completion; returns, in INPUT order, each
        request's full token list (prompt + new tokens).  Re-entrant: pool,
        allocator, and slot state are rebuilt per call."""
        with TraceAnnotation("serve.call"):
            return self._serve(requests)

    def _serve(self, requests: Sequence[Request]) -> List[List[int]]:
        self._t0 = time.perf_counter()
        for r in requests:
            if len(r.prompt) + r.max_new_tokens > self.slot_tokens:
                raise ValueError(
                    f"request needs {len(r.prompt) + r.max_new_tokens} "
                    f"tokens > slot_tokens={self.slot_tokens}")
            if self._lifetime_blocks(r) > self.n_blocks - 1:
                raise ValueError("request's lifetime block reservation "
                                 f"exceeds the pool ({self.n_blocks - 1} "
                                 "usable blocks)")
        order = sorted(range(len(requests)),
                       key=lambda i: (requests[i].arrival, i))
        pending = deque(order)
        results: List[Optional[_Slot]] = [None] * len(requests)
        eligible_s = [0.0] * len(requests)
        n_eligible = 0               # requests of `order` the clock reached
        self._prefill_rows = 0

        self._alloc = BlockAllocator(self.n_blocks)
        self._cache = None          # the last call's pool, freed first
        self._cache = init_paged_cache(self.cfg, self.n_blocks,
                                       self.block_size, self.slots)
        pool_bytes = paged_cache_nbytes(self._cache)
        self._bt = np.full((self.slots, self.nlog), -1, np.int32)
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self._cur = np.zeros((self.slots,), np.int32)
        self._pos = np.zeros((self.slots,), np.int32)
        self._shift = np.zeros((self.slots,), np.int32)
        self._done = np.ones((self.slots,), bool)
        self._keys = np.array(
            jnp.stack([jax.random.PRNGKey(0)] * self.slots))

        clock = 0
        chunks = 0
        temp = jnp.float32(self.temperature)
        eos = jnp.int32(-1 if self.eos_id is None else self.eos_id)
        while pending or any(s is not None for s in self._slots):
            now = time.perf_counter() - self._t0
            while (n_eligible < len(order)
                   and requests[order[n_eligible]].arrival <= clock):
                eligible_s[order[n_eligible]] = now
                n_eligible += 1
            # admit, strict arrival order, into free slots
            while pending and requests[pending[0]].arrival <= clock:
                free = [i for i, s in enumerate(self._slots) if s is None]
                if not free:
                    break
                idx = pending[0]
                st = self._try_admit(requests[idx], free[0], idx)
                if st is None:
                    break               # pool full: wait for a retirement
                results[idx] = st
                pending.popleft()
            if all(s is None for s in self._slots):
                # idle: jump the clock to the next arrival
                clock = max(clock + 1,
                            math.ceil(requests[pending[0]].arrival))
                continue

            with TraceAnnotation("serve.chunk"):
                self._run_chunk(temp, eos)
            chunks += 1
            clock += self.decode_chunk

        outs = []
        per_request = []
        for i, r in enumerate(requests):
            st = results[i]
            outs.append(list(r.prompt) + st.out)
            per_request.append({"eligible_s": eligible_s[i],
                                "seated_s": st.seated_s,
                                "first_s": st.first_s, "last_s": st.last_s,
                                "new_tokens": len(st.out)})
        lat = sorted(1e3 * (p["last_s"] - p["eligible_s"])
                     for p in per_request)
        self.stats = {
            "requests": len(requests),
            "new_tokens": sum(p["new_tokens"] for p in per_request),
            "prompt_tokens": sum(len(r.prompt) for r in requests),
            "prefill_rows": self._prefill_rows,
            "chunks": chunks,
            "steps": clock,
            "pool_bytes": pool_bytes,
            "peak_blocks": self._alloc.peak_used,
            "prefix_hits": self._alloc.prefix_hits,
            # eligible → last token on the host, wall clock
            "latency_ms_p50": lat[len(lat) // 2] if lat else 0.0,
            "latency_ms_p99": lat[min(len(lat) - 1,
                                      math.ceil(0.99 * len(lat)) - 1)]
            if lat else 0.0,
            "per_request": per_request,
        }
        return outs

    def _run_chunk(self, temp, eos) -> None:
        """One decode chunk: upload the slot state, run the chunk program,
        read its tokens back, emit them and retire finished requests."""
        with TraceAnnotation("serve.upload"):
            args = (jnp.asarray(self._bt), jnp.asarray(self._shift),
                    jnp.asarray(self._cur), jnp.asarray(self._done),
                    jnp.asarray(self._pos), jnp.asarray(self._keys))
        with self.engine._ctx():
            cur, done, self._cache, pos, keys, toks, emit = self._chunk_fn(
                self.engine.params, self._cache, *args, temp, eos)
        with TraceAnnotation("serve.readback"):
            self._cur, self._done = np.array(cur), np.array(done)
            self._pos, self._keys = np.array(pos), np.array(keys)
            toks, emit = np.asarray(toks), np.asarray(emit)
        now = time.perf_counter() - self._t0

        for t in range(self.decode_chunk):
            for i, st in enumerate(self._slots):
                if st is None or st.finished:
                    continue
                if emit[t, i]:
                    tok = int(toks[t, i])
                    st.out.append(tok)
                    hit_eos = (self.eos_id is not None
                               and tok == self.eos_id)
                    if hit_eos or len(st.out) >= st.req.max_new_tokens:
                        st.finished, st.last_s = True, now
        for i, st in enumerate(self._slots):
            if st is not None and st.finished:
                self._release(i, st)
