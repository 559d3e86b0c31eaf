"""The serving path's programs keep the names the benchmark's readers match.

A v5e trace names each program after the function `jax.jit` wrapped:
``bench/metrics/prefill_ms.py`` reads ``jit_prefill`` and
``bench/metrics/decode_step_ms.py`` reads ``jit_run``, the scheduler's
decode-chunk program and no other.  A refactor that renames one of them
would leave its metric silently empty; it fails here first.  (The
megakernel's custom-call name is guarded in `tests/test_v5e_compile.py`,
where it is compiled for the chip.)
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import transformer as T
from repro.serve import SlotScheduler
from repro.serve.paged_cache import init_paged_cache

SLOTS, BLOCK, SLOT_TOKENS = 2, 4, 16


@pytest.fixture(scope="module")
def sched():
    cfg = get_smoke_config("smollm-135m")
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    return SlotScheduler(cfg, params, slots=SLOTS, block_size=BLOCK,
                         slot_tokens=SLOT_TOKENS, decode_chunk=2)


def _module(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def _prefilled(eng):
    batch, _ = eng._pack([[1, 2, 3]])
    return batch, eng._prefill(eng.params, batch, smax=eng.smax)


def test_prefill_program_is_jit_prefill(sched):
    eng = sched.engine
    batch, _ = eng._pack([[1, 2, 3]])
    assert _module(eng._prefill.lower(eng.params, batch,
                                      smax=eng.smax)) == "jit_prefill"


def test_decode_step_program_is_jit_decode_step(sched):
    eng = sched.engine
    batch, (logits, cache, pos0) = _prefilled(eng)
    tokens = jnp.zeros((SLOTS, 1), jnp.int32)
    assert _module(eng._decode.lower(
        eng.params, cache, {"tokens": tokens}, pos0,
        positions=pos0 - batch["pad"])) == "jit_decode_step"


def test_chunk_program_alone_is_jit_run(sched):
    nlog = SLOT_TOKENS // BLOCK
    cache = init_paged_cache(sched.cfg, sched.n_blocks, BLOCK, SLOTS)
    ints = np.zeros((SLOTS,), np.int32)
    lowered = sched._chunk_fn.lower(
        sched.engine.params, cache, np.full((SLOTS, nlog), -1, np.int32),
        ints, ints, np.ones((SLOTS,), bool), ints,
        np.zeros((SLOTS, 2), np.uint32), jnp.float32(0.0), jnp.int32(-1))
    assert _module(lowered) == "jit_run"

    eng = sched.engine
    batch, (logits, cache, pos0) = _prefilled(eng)
    scan = eng._scan_fn(4, None).lower(eng.params, logits, cache,
                                       batch["pad"], pos0, jnp.int32(0),
                                       jnp.float32(0.0))
    assert _module(scan) == "jit_generate_scan"
