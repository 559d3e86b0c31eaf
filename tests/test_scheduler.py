"""Continuous-batching scheduler contracts (DESIGN.md §15).

The load-bearing promise is ARRIVAL-ORDER INVARIANCE: a greedy request's
tokens are bit-identical whether it runs alone through the static engine,
arrives in a full batch, or is admitted mid-flight into a slot freed by an
earlier retirement — for the float AND the residue-resident configs.  The
residue config is the hard case: requant round/clip boundaries amplify
1-ulp float drift into token flips, so any shape or masking leak between
slot-mates shows up as a failed bit-comparison here.

Everything else (prefix sharing, pool sizing, EOS retirement, re-entrancy)
must preserve that invariance, so each test closes the loop against
``sched.engine.generate([prompt])`` — the solo reference is generated by
the scheduler's OWN engine (``lanes == slots`` pins the decode batch width;
XLA reduction order is shape-dependent, see serve/engine.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.launch.costs import decode_cache_bytes
from repro.models import transformer as T
from repro.serve import Request, SlotScheduler

ARCHS = ["smollm-135m", "rns-smollm-135m-resident", "mamba2-1.3b"]

SLOTS, BLOCK, SLOT_TOKENS, CHUNK = 2, 4, 24, 2

_SCHEDS = {}


def _sched(arch, **kw):
    key = (arch, tuple(sorted(kw.items())))
    if key not in _SCHEDS:
        cfg = get_smoke_config(arch)
        params = T.make_params(cfg, jax.random.PRNGKey(0))
        _SCHEDS[key] = SlotScheduler(
            cfg, params, slots=SLOTS, block_size=BLOCK,
            slot_tokens=SLOT_TOKENS, decode_chunk=CHUNK, **kw)
    return _SCHEDS[key]


def _prompts(cfg, lens, seed=1, head=0):
    """Ragged random prompts; the first ``head`` tokens are shared (the
    prefix-cache bait — ``head`` should span whole blocks to be shareable)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab_size, head).tolist()
    return [shared + rng.integers(1, cfg.vocab_size, n - head).tolist()
            for n in lens]


def _solo(sched, prompts, news):
    return [sched.engine.generate([p], max_new_tokens=m)[0]
            for p, m in zip(prompts, news)]


@pytest.mark.parametrize("arch", ARCHS)
def test_arrival_order_invariance(arch):
    """Solo == full batch at t=0 == staggered mid-flight admission ==
    shuffled submission order, token-for-token."""
    sched = _sched(arch)
    prompts = _prompts(sched.cfg, [3, 9, 6, 11], head=0)
    news = [8, 3, 6, 4]
    solo = _solo(sched, prompts, news)

    burst = sched.serve([Request(p, m) for p, m in zip(prompts, news)])
    assert burst == solo, f"{arch}: batch-at-t0 diverged from solo"

    # staggered: with 2 slots, requests 2 and 3 are admitted into slots
    # freed by earlier retirements, mid-decode of their slot-mates
    stag = sched.serve([Request(p, m, arrival=a)
                        for p, m, a in zip(prompts, news, [0, 0, 3, 5])])
    assert stag == solo, f"{arch}: mid-flight admission changed tokens"

    # submission order must not matter: distinct arrivals, reversed input
    rev = sched.serve([Request(p, m, arrival=a) for p, m, a in
                       zip(prompts[::-1], news[::-1], [5, 3, 1, 0])])
    assert rev == solo[::-1], f"{arch}: submission order leaked into output"


def test_prefix_sharing_bit_identical_and_counted():
    """Sharing the prompt-head blocks changes WHERE keys are read from,
    never what they contain: outputs identical with sharing on/off, and the
    allocator actually counted hits (off → zero)."""
    on = _sched("smollm-135m")
    off = _sched("smollm-135m", prefix_sharing=False)
    prompts = _prompts(on.cfg, [10, 12, 9], seed=5, head=2 * BLOCK)
    reqs = [Request(p, 5) for p in prompts]
    a, b = on.serve(list(reqs)), off.serve(list(reqs))
    assert a == b == _solo(on, prompts, [5, 5, 5])
    assert on.stats["prefix_hits"] > 0
    assert off.stats["prefix_hits"] == 0
    # shared blocks are refcount-mapped, not copied: fewer physical blocks
    assert on.stats["peak_blocks"] < off.stats["peak_blocks"]


def test_tiny_pool_defers_admission_then_reuses_freed_blocks():
    """A pool far below slots·slot_tokens still serves everything: admission
    defers when the lifetime reservation cannot be covered and proceeds on
    the freed blocks of retired requests."""
    cfg = get_smoke_config("smollm-135m")
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    full = SLOTS * (SLOT_TOKENS // BLOCK)        # static reservation
    n_blocks = 1 + full // 2
    sched = SlotScheduler(cfg, params, slots=SLOTS, block_size=BLOCK,
                          slot_tokens=SLOT_TOKENS, decode_chunk=CHUNK,
                          n_blocks=n_blocks)
    prompts = _prompts(cfg, [11, 9, 12, 10, 8], seed=7)
    news = [6, 8, 4, 7, 5]
    outs = sched.serve([Request(p, m) for p, m in zip(prompts, news)])
    assert outs == _solo(sched, prompts, news)
    assert sched.stats["peak_blocks"] <= n_blocks - 1   # block 0 is trash
    # and the paged pool really is smaller than the static engine's cache
    assert sched.stats["pool_bytes"] < decode_cache_bytes(
        cfg, SLOTS, SLOT_TOKENS)


def test_eos_retires_slot_and_matches_engine():
    """EOS mid-stream retires the slot (blocks freed for the queue) and
    truncates exactly where the solo engine truncates."""
    base = _sched("smollm-135m")
    prompts = _prompts(base.cfg, [7, 5, 10], seed=11)
    ref = _solo(base, prompts, [8, 8, 8])
    eos = ref[0][len(prompts[0]) + 3]            # 4th new token of request 0
    sched = _sched("smollm-135m", eos_id=int(eos))
    outs = sched.serve([Request(p, 8) for p in prompts])
    solo = [sched.engine.generate([p], max_new_tokens=8, eos_id=int(eos))[0]
            for p in prompts]
    assert outs == solo
    assert len(outs[0]) < len(prompts[0]) + 8    # actually stopped early


def test_serve_is_reentrant():
    sched = _sched("smollm-135m")
    prompts = _prompts(sched.cfg, [4, 8], seed=13)
    reqs = [Request(p, 6, arrival=a) for p, a in zip(prompts, [0, 2])]
    first = sched.serve(list(reqs))
    second = sched.serve(list(reqs))
    assert first == second == _solo(sched, prompts, [6, 6])


def test_ring_cache_architectures_rejected_at_construction():
    """Sliding-window stacks share a ring-cache write cursor across the
    batch — unservable per-slot; the scheduler fails fast (DESIGN.md §15)."""
    cfg = get_smoke_config("h2o-danube-1.8b")
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        SlotScheduler(cfg, params, slots=SLOTS, block_size=BLOCK,
                      slot_tokens=SLOT_TOKENS)


def test_oversized_requests_rejected_up_front():
    sched = _sched("smollm-135m")
    fits = SLOT_TOKENS // 2
    with pytest.raises(ValueError):              # slot capacity
        sched.serve([Request(list(range(1, SLOT_TOKENS)), 8)])
    tiny = SlotScheduler(sched.cfg,
                         T.make_params(sched.cfg, jax.random.PRNGKey(0)),
                         slots=SLOTS, block_size=BLOCK,
                         slot_tokens=SLOT_TOKENS, decode_chunk=CHUNK,
                         n_blocks=3)
    with pytest.raises(ValueError):              # pool lifetime reservation
        tiny.serve([Request(list(range(1, fits)), fits)])


@pytest.mark.parametrize("shift", [0, 5])
def test_paged_view_matches_left_padded_layout(shift):
    """Decode attends over a view of the pool that holds logical position r
    at slot r + shift — the solo engine's left-padded cache layout, where r
    sits at r + pad.  Identical slots keep an accelerator's key-axis
    reductions (softmax sum, p·V) in the same order as the solo reference;
    rows before the shift, past the last write or in unmapped blocks are
    invalid (kpos −1)."""
    bs, nlog, written = 4, 4, 9                     # logical 0..9 valid
    rng = np.random.default_rng(0)
    pool = rng.standard_normal((6, bs, 1, 2)).astype(np.float32)
    table = np.array([[3, 1, 4, -1]], np.int32)     # logical blocks 0..2
    k, v, kpos = T.paged_view(pool, pool, table, np.array([written]),
                              np.array([shift]))
    k, kpos = np.asarray(k), np.asarray(kpos)[0]
    for s in range(nlog * bs):
        r = s - shift
        if 0 <= r <= written:
            assert kpos[s] == r
            assert np.array_equal(k[0, s], pool[table[0, r // bs], r % bs])
        else:
            assert kpos[s] == -1


def test_paged_decode_step_writes_only_the_steps_rows():
    """One paged decode step with two live slots (one shifted), a retired
    slot (no mapped block) and a slot whose write position falls in an
    unmapped block.  The pool comes back changed only at the live slots'
    (layer, block, row) write rows and in trash block 0; those rows hold the
    step's K/V, and the live slots' logits are bit-identical to the
    write-then-gather order: the step's K/V written into the pool first,
    then every layer attending over `paged_view` of the written pool (the
    contiguous decode path over that view)."""
    from repro.serve.paged_cache import init_paged_cache

    cfg = get_smoke_config("smollm-135m")
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    nlog, n_phys, L = 6, 20, cfg.n_blocks
    Hk, dh = cfg.num_kv_heads, cfg.head_dim
    rng = np.random.default_rng(3)
    pool = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        init_paged_cache(cfg, n_phys, BLOCK, 4))
    table = np.full((4, nlog), -1, np.int32)
    table[0, :5] = [3, 7, 1, 12, 9]     # live: writes position 17
    table[1, :4] = [5, 2, 18, 11]       # live, view shifted by 4: writes 13
    table[3, :2] = [4, 6]               # position 13's block is unmapped
    positions = np.array([17, 13, 9, 13], np.int32)   # slot 2: retired
    shift = np.array([0, 4, 8, 4], np.int32)          # view slot 17 for all
    live = [0, 1]
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 1)), jnp.int32)

    logits, out = T.decode_step(cfg, params, pool, {"tokens": tokens},
                                jnp.asarray(positions + shift),
                                positions=jnp.asarray(positions),
                                block_tables=jnp.asarray(table))

    def kv(pool):                       # (L, n_phys, block, 2, Hk, dh)
        return np.asarray(pool["sub0"]["kv"], np.float32).reshape(
            L, n_phys, BLOCK, 2, Hk, dh)

    def contiguous(pool):               # each layer's paged_view, stacked
        views = [T.paged_view(*(jnp.asarray(kv(pool)[i, :, :, j])
                                for j in range(2)),
                              jnp.asarray(table), jnp.asarray(positions),
                              jnp.asarray(shift))[:2] for i in range(L)]
        return {"sub0": {n: jnp.stack([v[j] for v in views]).astype(
            pool["sub0"]["kv"].dtype) for j, n in enumerate(("k", "v"))}}

    def solo(pool):
        return T.decode_step(cfg, params, contiguous(pool),
                             {"tokens": tokens}, jnp.int32(17),
                             positions=jnp.asarray(positions))

    # the step's own K/V, from the contiguous path over the incoming pool
    _, first = solo(pool)
    w = kv(pool)
    for b in live:
        lp = positions[b]
        for j, n in enumerate(("k", "v")):
            w[:, table[b, lp // BLOCK], lp % BLOCK, j] = np.asarray(
                first["sub0"][n], np.float32)[:, b, 17]
    written = {"sub0": {"kv": jnp.asarray(
        w.reshape(pool["sub0"]["kv"].shape), pool["sub0"]["kv"].dtype)}}
    ref_logits, _ = solo(written)

    assert np.array_equal(kv(out)[:, 1:], kv(written)[:, 1:])
    np.testing.assert_array_equal(np.asarray(logits)[live],
                                  np.asarray(ref_logits)[live])
