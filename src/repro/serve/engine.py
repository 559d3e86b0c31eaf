"""Batched serving engine: mask-correct prefill + on-device scan decode.

This is the STATIC engine — pack-once/run-once: requests are left-padded
(right-aligned) to a common prefill length, decoded together, and every
sequence waits for the slowest batchmate while ``smax`` KV slots stay
reserved per sequence.  It is the bit-reference and measured baseline;
continuous batching — mid-flight admission into freed slots over a paged,
prefix-shared KV pool — lives in `serve.scheduler.SlotScheduler`
(DESIGN.md §15), which reuses this engine's prefill and weight encoding.

Ragged prompts batch correctly through a per-sequence validity mask —
threaded through `models.transformer.prefill` as ``batch["pad"]`` — so pad
slots are invalid attention keys, per-sequence RoPE positions are
``arange(S) − pad[i]``, and SSM layers zero padded inputs; greedy outputs
are *batch-invariant* (bit-identical whether a prompt is served alone or
alongside longer batchmates; `tests/test_serve.py`).

Decode runs as ONE jitted `lax.scan` over the new-token axis: sampling, the
per-sequence EOS/done mask, and the KV/SSM cache updates all live on device,
and the sampled tokens are materialized to the host once at the end — zero
per-token host round-trips (DESIGN.md §11).  The per-token Python loop
survives as ``engine="host"`` for A/B measurement (`benchmarks/
decode_bench.py`) and equivalence testing; both paths share prefill /
`decode_step`, so they emit identical greedy tokens.

Compile-cache bounds: the decode scan is keyed on ``(max_new_tokens,
eos_id)`` only — temperature and seed are traced operands — and the cache
is a small LRU; prefill lengths are bucketed to powers of two (floor 8,
rounded up to ``ssm_chunk`` where the stack needs it), so a ragged workload
compiles a handful of prefill shapes, not one per prompt length.

Sampling: greedy or temperature; deterministic under a fixed seed (the root
key is split once before first use, then chain-split per step — the same
chain in both engines).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.rns_tensor import encode_params
from repro.models import transformer as T

__all__ = ["Engine"]

# decode-scan executables kept per engine: (max_new_tokens, eos_id) pairs.
_SCAN_CACHE_MAX = 8


def _sample(logits, temperature: float, key):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature,
                                  axis=-1).astype(jnp.int32)


def _sample_traced(logits, temperature, key):
    """`_sample` with the temperature as a TRACED operand: t ≤ 0 selects
    greedy via `where`, so one executable serves every temperature (the
    divide uses a safe denominator on the greedy branch; for t > 0 the
    scaled logits — and hence the sampled bits — match `_sample` exactly)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.asarray(temperature, jnp.float32)
    scaled = logits / jnp.where(t > 0.0, t, 1.0)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(t > 0.0, sampled, greedy)


def bucket_plen(cfg: ModelConfig, plen: int) -> int:
    """Bucket a padded prompt length: next power of two (floor 8), then
    rounded up to ``ssm_chunk`` where the stack requires chunk-aligned
    prefill.  Extra pad slots are provably inert (DESIGN.md §11), so
    bucketing changes compile-cache pressure, never tokens."""
    b = 8
    while b < plen:
        b *= 2
    if cfg.ssm or cfg.hybrid:
        q = cfg.ssm_chunk
        b = -(-b // q) * q
    return b


class Engine:
    """Serving engine.  ``params`` is the raw checkpoint pytree; when the
    config's :class:`~repro.core.LinearSpec` asks for encoded weights
    (``encode_weights=True`` with an rns_int8 backend), the linear weights
    are quantized + forward-converted to residue-domain
    :class:`~repro.core.RNSTensor`s ONCE here (`rns_tensor.encode_params`,
    DESIGN.md §12) — prefill and the decode scan then consume residues
    directly and perform zero weight quantizations / forward conversions per
    step, with greedy outputs bit-identical to the live-quantization path.

    Fused-backend configs resolve the megakernel tiling of their decode
    shapes at init (`kernels.tune.warm_for_config`): a row of the committed
    `benchmarks/tune_table.json` (read from the checkout) or the static
    default — init and tracing never sweep.  ``self.tune_report`` records
    per shape whether the table hit.
    """

    def __init__(self, cfg: ModelConfig, params, smax: int = 2048,
                 lanes: Optional[int] = None, verify: Optional[str] = None,
                 mesh=None, dist_layout: Optional[str] = None):
        if verify not in (None, "static"):
            raise ValueError(f"verify={verify!r}: expected None or 'static'")
        if verify == "static":
            # Opt-in static gate (DESIGN.md §16): re-derive and prove every
            # bound/launch this config's decode path relies on before any
            # weight is encoded; raises AnalysisError naming the violation.
            from repro.analysis import check_config

            check_config(cfg).raise_if_failed()
        self.cfg = cfg
        # Decode-lane bucket: every packed batch is right-padded with fully-
        # padded dummy rows to a multiple of ``lanes``.  XLA's reduction
        # order inside a matmul depends on the operand SHAPES, so a prompt
        # decoded at B=1 and the same prompt in a B=4 slot batch can differ
        # in the last ulp — enough to flip a greedy argmax once amplified
        # through the residue chain's round/clip boundaries.  Pinning the
        # lane count makes greedy outputs batch-width-invariant by
        # construction; the SlotScheduler sets ``lanes=slots`` so its solo
        # bit-reference (`sched.engine.generate([prompt])`) runs the exact
        # shapes of the slot chunk.
        self.lanes = None if lanes is None else int(lanes)
        spec = cfg.linear_spec
        # Multi-device serving (repro.dist, DESIGN.md §17): a mesh turns on
        # the sharded launch path — every fused megakernel call inside
        # prefill / the decode scan routes through
        # `dist.rns_shard.sharded_fused_matmul` while the context below is
        # active, with greedy outputs bit-identical to the single-device
        # engine (the parity contract, tests/test_dist.py).
        self._dist_ctx = None
        if mesh is not None:
            from repro.dist import engine as _dist_engine

            self._dist_ctx = _dist_engine.make_context(cfg, mesh,
                                                       layout=dist_layout)
        elif dist_layout is not None:
            raise ValueError("dist_layout= without mesh=: pass the mesh the "
                             "layout should shard over")
        # Residue-resident configs (DESIGN.md §14) need the chained MLP's
        # weights in the chain basis — sized for the gated down-product
        # bound d_ff·127³, shared by every launch in the chain — while
        # attention keeps the per-K default.
        gb = None
        if spec.is_rns and spec.encode_weights:
            if spec.domain == "residue" and cfg.glu and cfg.d_ff > 0:
                from repro.core.rns import basis_for_chain

                gb = {"mlp": basis_for_chain(cfg.d_ff)}
        if self._dist_ctx is not None:
            from repro.dist import engine as _dist_engine

            # One-time SHARDED encode + placement: the encode itself runs
            # under jit(out_shardings=...), so each device forward-converts
            # only its slice of every weight (dist/engine.place_params).
            params = _dist_engine.place_params(self._dist_ctx, cfg, params,
                                               group_basis=gb)
        elif spec.is_rns and spec.encode_weights:
            params = encode_params(params, backend=spec.backend,
                                   group_basis=gb)
        self.params = params
        self.smax = smax
        # named functions, not partials: a jitted partial compiles to a
        # program named `jit__unknown`, and the benchmark's readers find
        # `jit_prefill` / `jit_decode_step` in the device trace by name
        def prefill(params, batch, smax):
            return T.prefill(cfg, params, batch, smax=smax)

        def decode_step(params, cache, batch, pos, positions=None):
            return T.decode_step(cfg, params, cache, batch, pos,
                                 positions=positions)

        self._decode = jax.jit(decode_step)
        self._prefill = jax.jit(prefill, static_argnames=("smax",))
        self._scan_fns: "OrderedDict[Any, Any]" = OrderedDict()
        self.prefill_shapes = set()          # distinct (B, plen) compiled
        from repro.kernels import tune

        self.tune_report = tune.warm_for_config(cfg)

    def _ctx(self):
        """The engine's dist-context activation (a null context when
        single-device).  Wrapped around every jit invocation site so the
        TRACE — where `core.rns_linear`'s fused branches consult
        `dist.context.current()` — sees the engine's mesh; already-compiled
        executables are unaffected by the wrapper."""
        if self._dist_ctx is None:
            import contextlib

            return contextlib.nullcontext()
        from repro.dist import context as _dc

        return _dc.use(self._dist_ctx)

    # ------------------------------------------------------------- batching -
    def _pack(self, prompts: List[List[int]]):
        """Right-align (left-pad) ragged prompts to a common BUCKETED length.

        The padded length is `bucket_plen`'s power-of-two bucket (floor 8),
        rounded up to ``ssm_chunk`` for SSM/hybrid stacks (the chunked dual
        form's requirement) — so a ragged workload compiles O(log smax)
        prefill shapes instead of one per distinct prompt length.  Pad slots
        are provably inert.
        """
        B = len(prompts)
        L = B if self.lanes is None else self.lanes * (-(-B // self.lanes))
        plen = bucket_plen(self.cfg, max(len(p) for p in prompts))
        # dummy lanes (B..L) are FULLY padded: every key invalid, outputs
        # never read — they exist only to pin the decode batch width.
        toks = np.zeros((L, plen), np.int32)
        pad = np.full((L,), plen, np.int32)
        for i, p in enumerate(prompts):
            toks[i, plen - len(p):] = p
            pad[i] = plen - len(p)
        self.prefill_shapes.add((L, plen))
        return {"tokens": jnp.asarray(toks), "pad": jnp.asarray(pad)}, plen

    # ------------------------------------------------------------- generate -
    def generate(self, prompts: List[List[int]], max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 eos_id: Optional[int] = None,
                 engine: str = "scan") -> List[List[int]]:
        """Batched generation.  prompts: ragged token lists.

        ``engine="scan"`` (default) runs the fully on-device decode;
        ``"host"`` runs the per-token Python loop (same math, per-token
        dispatch + host syncs — the measured baseline).
        """
        if engine not in ("scan", "host"):
            raise ValueError(f"engine must be 'scan' or 'host', got {engine!r}")
        batch, plen = self._pack(prompts)
        if engine == "host":
            return self._generate_host(prompts, batch, plen, max_new_tokens,
                                       temperature, seed, eos_id)
        # prefill through the same jitted executable as the host path (one
        # compile per batch shape, shared); the decode scan is keyed on
        # (max_new_tokens, eos_id) only — temperature and seed ride along
        # as traced operands.
        with self._ctx():
            logits, cache, pos0 = self._prefill(self.params, batch,
                                                smax=self.smax)
            run = self._scan_fn(max_new_tokens, eos_id)
            first, done0, toks, emit, _ = run(self.params, logits, cache,
                                              batch["pad"], pos0,
                                              jnp.int32(seed),
                                              jnp.float32(temperature))
        first = np.asarray(first)
        toks = np.asarray(toks)                       # (T-1, B)
        emit = np.asarray(emit)                       # (T-1, B) bool
        out = [list(p) for p in prompts]
        for i in range(len(prompts)):
            out[i].append(int(first[i]))
            for t in range(toks.shape[0]):
                if emit[t, i]:
                    out[i].append(int(toks[t, i]))
        return out

    # ------------------------------------------------------------ scan path -
    def _scan_fn(self, max_new_tokens: int, eos_id: Optional[int]):
        """The decode-scan executable for (max_new_tokens, eos_id).

        Temperature and seed are traced operands of the returned function —
        serving sweeps over sampling parameters reuse ONE executable — and
        the per-engine cache is a bounded LRU (oldest executable dropped
        past `_SCAN_CACHE_MAX` keys)."""
        key_ = (int(max_new_tokens), eos_id)
        if key_ in self._scan_fns:
            self._scan_fns.move_to_end(key_)
            return self._scan_fns[key_]
        cfg = self.cfg
        eos = -1 if eos_id is None else int(eos_id)   # -1 never matches

        # not `run`: `jit_run` names the scheduler's chunk program alone
        def generate_scan(params, logits, cache, pad, pos0, seed,
                          temperature):
            key, k0 = jax.random.split(jax.random.PRNGKey(seed))
            first = _sample_traced(logits, temperature, k0)
            done0 = first == eos
            if max_new_tokens <= 1:
                zero = jnp.zeros((0, pad.shape[0]), jnp.int32)
                return first, done0, zero, zero.astype(bool), cache

            def chain(k, _):
                k, sub = jax.random.split(k)
                return k, sub

            _, subkeys = jax.lax.scan(chain, key, None,
                                      length=max_new_tokens - 1)

            def step(carry, xs):
                cur, done, cache, t = carry
                kt = xs
                logits, cache = T.decode_step(
                    cfg, params, cache, {"tokens": cur[:, None]}, t,
                    positions=t - pad)
                nxt = _sample_traced(logits, temperature, kt)
                new_done = done | (nxt == eos)
                # emit == "was not done at entry": EOS itself is emitted,
                # everything after it is dropped host-side.
                return (nxt, new_done, cache, t + 1), (nxt, ~done)

            (_, _, cache, _), (toks, emit) = jax.lax.scan(
                step, (first, done0, cache, pos0), subkeys)
            # the final cache is returned ONLY so the donated prefill cache
            # (donate_argnums below) aliases an output and XLA can actually
            # reuse its buffers for the scan carry — callers discard it.
            return first, done0, toks, emit, cache

        # Donate the cache: the prefill output's KV/SSM buffers are dead the
        # moment the scan starts, so aliasing them into the scan carry
        # removes one full cache copy from peak HBM and the per-step
        # defensive copies XLA would otherwise emit (tests/test_serve.py
        # asserts the donation is warning-free, i.e. actually usable).
        fn = jax.jit(generate_scan, donate_argnums=(2,))
        self._scan_fns[key_] = fn
        while len(self._scan_fns) > _SCAN_CACHE_MAX:
            self._scan_fns.popitem(last=False)
        return fn

    # ------------------------------------------------------------ host path -
    def _generate_host(self, prompts, batch, plen, max_new_tokens,
                       temperature, seed, eos_id):
        """Per-token Python loop (the pre-scan engine, kept as the measured
        baseline): one jitted decode_step dispatch + `int()` host syncs per
        token.  Mask-correct — it shares prefill/decode_step with the scan
        path — and emits the identical token stream."""
        B = len(prompts)
        pad = batch["pad"]
        with self._ctx():
            logits, cache, _ = self._prefill(self.params, batch,
                                             smax=self.smax)
        key, k0 = jax.random.split(jax.random.PRNGKey(seed))
        cur = _sample(logits, temperature, k0)
        out = [list(p) for p in prompts]
        done = np.zeros(B, bool)
        for i in range(B):
            tok = int(cur[i])
            out[i].append(tok)
            if eos_id is not None and tok == eos_id:
                done[i] = True

        for t in range(1, max_new_tokens):
            if done.all():
                break
            pos = jnp.int32(plen + t - 1)
            with self._ctx():
                logits, cache = self._decode(self.params, cache,
                                             {"tokens": cur[:, None]}, pos,
                                             positions=pos - pad)
            key, sub = jax.random.split(key)
            cur = _sample(logits, temperature, sub)
            for i in range(B):
                if not done[i]:
                    tok = int(cur[i])
                    out[i].append(tok)
                    if eos_id is not None and tok == eos_id:
                        done[i] = True
        return out
