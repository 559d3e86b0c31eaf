"""End-to-end system behaviour: train → checkpoint → serve on one box."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_smoke_config
from repro.data.pipeline import batch_for_step
from repro.models import transformer as T
from repro.serve.engine import Engine
from repro.train import checkpoint as ckpt
from repro.train.optimizer import make_optimizer
from repro.train.runtime import TrainLoop
from repro.train.trainstep import make_train_step


def test_train_checkpoint_serve_cycle():
    """The full lifecycle a deployment runs: train, crash-resume, serve."""
    cfg = get_smoke_config("smollm-135m")
    key = jax.random.PRNGKey(0)
    params = T.make_params(cfg, key)
    opt = make_optimizer(cfg, total_steps=50, base_lr=1e-2, warmup=5)
    step = jax.jit(make_train_step(cfg, opt))

    def batch_fn(s):
        b = batch_for_step(0, s, 8, 32, cfg.vocab_size)
        return {k: jnp.asarray(v) for k, v in b.items()}

    with tempfile.TemporaryDirectory() as d:
        loop = TrainLoop(train_step=step, batch_fn=batch_fn, params=params,
                         opt_state=opt.init(params), workdir=d,
                         ckpt_every=25)
        res = loop.run(50)
        assert res["losses"][-1] < res["losses"][0]

        # "crash" and restart: a new incarnation resumes from step 50
        loop2 = TrainLoop(train_step=step, batch_fn=batch_fn, params=params,
                          opt_state=opt.init(params), workdir=d,
                          ckpt_every=25)
        assert loop2.start_step == 50

        # serve from the trained params
        eng = Engine(cfg, loop.params, smax=64)
        outs = eng.generate([[1, 2, 3], [7]], max_new_tokens=6)
        assert len(outs) == 2
        assert len(outs[0]) == 3 + 6 and len(outs[1]) == 1 + 6
        assert all(0 <= t < cfg.vocab_size for o in outs for t in o)

        # metrics were written
        assert os.path.exists(os.path.join(d, "metrics.jsonl"))


def test_generation_deterministic():
    cfg = get_smoke_config("smollm-135m")
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, smax=32)
    a = eng.generate([[1, 2]], max_new_tokens=5, temperature=0.7, seed=3)
    b = eng.generate([[1, 2]], max_new_tokens=5, temperature=0.7, seed=3)
    assert a == b


def test_sigterm_emergency_save():
    import signal
    cfg = get_smoke_config("smollm-135m")
    params = T.make_params(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(cfg, total_steps=100, base_lr=1e-3, warmup=1)
    raw_step = jax.jit(make_train_step(cfg, opt))
    hits = {"n": 0}

    def step(params, state, batch, s):
        hits["n"] += 1
        if hits["n"] == 3:                     # simulate preemption notice
            os.kill(os.getpid(), signal.SIGTERM)
        return raw_step(params, state, batch, s)

    def batch_fn(s):
        b = batch_for_step(0, s, 4, 16, cfg.vocab_size)
        return {k: jnp.asarray(v) for k, v in b.items()}

    with tempfile.TemporaryDirectory() as d:
        loop = TrainLoop(train_step=step, batch_fn=batch_fn, params=params,
                         opt_state=opt.init(params), workdir=d,
                         ckpt_every=0)          # only the emergency save
        res = loop.run(100)
        # stopped early and saved
        assert res["last_step"] < 99
        assert ckpt.latest_step(os.path.join(d, "ckpt")) == res["last_step"]


def test_compile_cache_placement(monkeypatch):
    """`enable_compile_cache`: an outside JAX_COMPILATION_CACHE_DIR is left
    to JAX; otherwise ONE fixed, git-ignored directory in the checkout."""
    import pathlib

    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    where = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", where)]
    assert where == compile_cache.enable_compile_cache()     # fixed path
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(where) == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
