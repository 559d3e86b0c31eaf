"""Training CLI — the launcher a cluster job invokes.

On real hardware this runs under `jax.distributed.initialize()` with the
production mesh; on this CPU container it runs the same code path on the
host mesh (1 device) — which is exactly what the end-to-end example uses.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 300 --batch 8 --seq 256 --workdir /tmp/run1

Features exercised: sharded params (NamedSharding via the production
policy), fault-tolerant TrainLoop (auto-resume, SIGTERM save, straggler
watchdog), stateless data pipeline, optional RNS-int8 backend and gradient
compression.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, get_smoke_config
from repro.data.pipeline import batch_for_step
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import batch_specs, param_specs, shardings
from repro.models import transformer as T
from repro.train.optimizer import make_optimizer
from repro.train.runtime import TrainLoop
from repro.train.trainstep import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default="/tmp/repro-train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--d-model", type=int, default=None,
                    help="optional width override (examples use this)")
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    over = {}
    if args.d_model:
        over["d_model"] = args.d_model
    if args.layers:
        over["num_layers"] = args.layers
    if over:
        cfg = dataclasses.replace(cfg, **over)

    mesh = make_host_mesh()
    key = jax.random.PRNGKey(args.seed)
    params = T.make_params(cfg, key)
    opt = make_optimizer(cfg, total_steps=args.steps, base_lr=args.lr)
    opt_state = opt.init(params)

    psh = shardings(mesh, param_specs(mesh, cfg, params))
    params = jax.device_put(params, psh)
    osh = shardings(mesh, param_specs(mesh, cfg, opt_state))
    opt_state = jax.device_put(opt_state, osh)

    step_fn = jax.jit(make_train_step(cfg, opt, n_micro=args.n_micro),
                      donate_argnums=(0, 1))

    def batch_fn(step):
        b = batch_for_step(args.seed, step, args.batch, args.seq,
                           cfg.vocab_size)
        if cfg.frontend == "embeddings":
            # frontend stub: derive deterministic embeddings from token ids
            tok = jnp.asarray(b["tokens"])
            emb = jax.nn.one_hot(tok % cfg.d_model, cfg.d_model,
                                 dtype=jnp.bfloat16)
            return {"embeds": emb, "labels": jnp.asarray(b["labels"])}
        return {k: jnp.asarray(v) for k, v in b.items()}

    def shard_fn(tree):
        return jax.device_put(tree, shardings(
            mesh, param_specs(mesh, cfg, tree)))

    loop = TrainLoop(train_step=step_fn, batch_fn=batch_fn, params=params,
                     opt_state=opt_state, workdir=args.workdir,
                     ckpt_every=args.ckpt_every, shard_fn=shard_fn)
    res = loop.run(args.steps)
    tokens = args.batch * args.seq
    print(json.dumps({
        "arch": cfg.name, "steps_run": len(res["losses"]),
        "first_loss": res["losses"][0] if res["losses"] else None,
        "last_loss": res["losses"][-1] if res["losses"] else None,
        "stragglers": res["stragglers"],
        "tokens_per_step": tokens,
    }, indent=2))
    return res


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
