"""The plain reference against the program at a small size on the CPU: the
same weights from the same seed, and, with the program's model run in
float32 at `highest` precision, the same logits."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from bench import harness, reference

import tiny


@pytest.fixture(scope="module")
def config():
    path = harness.BENCH / "configs" / "smollm-135m.json"
    return dict(json.loads(path.read_text()), **tiny.SIZES)


def test_weights_follow_the_programs_recipe(config):
    from repro.models import transformer as T

    cfg = harness.program_config(config)
    P = jax.jit(lambda k: T.make_params(cfg, k))(jax.random.PRNGKey(77))
    W = reference.make_weights(config, 77, "bfloat16")
    assert np.array_equal(np.asarray(P["embed"]), np.asarray(W["embed"]))
    blk = P["blocks"]["sub0"]
    for name in ("wq", "wk", "wv", "wo"):
        assert np.array_equal(np.asarray(blk["attn"][name]),
                              np.asarray(W["layers"][name]))
    for name in ("w_gate", "w_up", "w_down"):
        assert np.array_equal(np.asarray(blk["mlp"][name]),
                              np.asarray(W["layers"][name]))


def test_logits_match_the_program_in_float32(config):
    from repro.models import transformer as T

    cfg = dataclasses.replace(harness.program_config(config),
                              param_dtype="float32")
    W = reference.make_weights(config, 5, "bfloat16")
    P = jax.jit(lambda k: T.make_params(harness.program_config(config), k))(
        jax.random.PRNGKey(5))
    P = jax.tree.map(lambda x: x.astype(np.float32), P)
    toks = np.random.default_rng(0).integers(1, config["vocab_size"],
                                             (2, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = T.forward(cfg, P, {"tokens": toks})
    shape = tuple(sorted(reference.shape_of(config).items()))
    got = reference.logits(W, toks, shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_gap_of_the_reference_own_choice_is_zero(config):
    W = reference.make_weights(config, 3, "bfloat16")
    shape = tuple(sorted(reference.shape_of(config).items()))
    prompt = [5, 9, 17, 33]
    toks = np.zeros((1, 12), np.int32)
    toks[0, :4] = prompt
    new = []
    for t in range(4, 12):                    # greedy on the reference
        lg = reference.logits(W, toks, shape)[0, t - 1]
        nxt = int(np.argmax(np.asarray(lg)))
        new.append(nxt)
        toks[0, t] = nxt
    tokens, served = harness.pack([(prompt, new)], 16)
    g = np.asarray(reference.gaps(W, tokens, served, shape))
    assert g.max() == 0.0 and (served >= 0).sum() == len(new)
    wrong = [(t + 1) % config["vocab_size"] for t in new]
    tokens, served = harness.pack([(prompt, wrong)], 16)
    assert np.asarray(reference.gaps(W, tokens, served, shape)).max() > 0
