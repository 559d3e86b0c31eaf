import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
#   Set here only — smoke tests and benchmarks must see 1 device.

"""Multi-pod dry-run driver.

For every (arch × shape × mesh) cell:
  1. build the production mesh (16×16 single pod / 2×16×16 multi-pod),
  2. build abstract params/optimizer/caches (jax.eval_shape — no allocation),
  3. jit the right step with explicit in/out shardings:
        train_4k     → train_step (fwd + bwd + optimizer update)
        prefill_32k  → forward    (full-sequence logits)
        decode_*     → decode_step (one token against an S-sized cache)
  4. .lower().compile() — sharding mismatches, compile-time OOMs or
     unsupported collectives fail the cell (they are bugs in the system),
  5. record memory_analysis / cost_analysis / per-op collective wire bytes
     into a JSONL file (incremental + resumable: done cells are skipped).

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun.jsonl]
"""
import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, get_config, list_archs
from repro.launch import roofline as RL
from repro.launch.inputs import abstract_cache, abstract_params, input_specs
from repro.launch.mesh import auto_mesh, dp_axes, make_production_mesh
from repro.launch.sharding import (batch_specs, cache_specs, logits_spec,
                                   mode_for, param_specs, shardings)
from repro.models import transformer as T
from repro.train.optimizer import make_optimizer
from repro.train.trainstep import make_train_step


def _mesh_name(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               mode: str | None = None, extra_tag: str = "",
               overrides: dict | None = None,
               mesh_split: tuple | None = None):
    """Lower+compile one cell; returns the JSONL record (never raises)."""
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": _mesh_name(multi_pod),
           "tag": extra_tag, "status": "ok"}
    if shape_name in cfg.skip_shapes:
        rec.update(status="skip",
                   reason="full-attention arch: no sub-quadratic structure "
                          "for 500k decode (DESIGN.md §Arch-applicability)")
        return rec
    try:
        t0 = time.time()
        if mesh_split:
            # logical re-factorization of the same physical pod(s): e.g.
            # (64, 4) maps the 256 chips as 64-way data × 4-way model
            dd, mm = mesh_split
            if multi_pod:
                mesh = auto_mesh((2, dd, mm), ("pod", "data", "model"))
            else:
                mesh = auto_mesh((dd, mm), ("data", "model"))
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        mode = mode or mode_for(cfg)
        rec["mode"] = mode
        rec["n_devices"] = mesh.size
        n_params = T.count_params(cfg)
        n_active = T.active_params(cfg)
        rec["n_params"] = n_params
        rec["n_active"] = n_active
        rec["model_flops"] = RL.model_flops_for(cfg, shape, n_params, n_active)

        params_abs = abstract_params(cfg)
        pspec = param_specs(mesh, cfg, params_abs, mode)
        psh = shardings(mesh, pspec)
        batch_abs = input_specs(cfg, shape)
        bsh = shardings(mesh, batch_specs(mesh, cfg, batch_abs, mode))

        if shape.kind == "train":
            opt = make_optimizer(cfg)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            osh = shardings(mesh, param_specs(mesh, cfg, opt_abs, mode))
            step = make_train_step(cfg, opt)
            jf = jax.jit(step,
                         in_shardings=(psh, osh, bsh, None),
                         out_shardings=(psh, osh, None))
            with mesh:
                lowered = jf.lower(params_abs, opt_abs, batch_abs,
                                   jnp.int32(0))
        elif shape.kind == "prefill":
            fwd = functools.partial(T.forward, cfg)
            lsh = shardings(
                mesh, jax.tree.map(
                    lambda _: logits_spec(mesh, cfg, shape.global_batch),
                    jnp.zeros(())))
            jf = jax.jit(fwd, in_shardings=(psh, bsh),
                         out_shardings=None)
            with mesh:
                lowered = jf.lower(params_abs, batch_abs)
        else:  # decode
            cache_abs = abstract_cache(cfg, shape.global_batch, shape.seq_len)
            csh = shardings(mesh, cache_specs(mesh, cfg, cache_abs))
            dec = functools.partial(T.decode_step, cfg)
            jf = jax.jit(dec, in_shardings=(psh, csh, bsh, None),
                         out_shardings=None)
            with mesh:
                lowered = jf.lower(params_abs, cache_abs, batch_abs,
                                   jnp.int32(shape.seq_len - 1))
        rec["lower_s"] = time.time() - t0

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1

        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float))
                       and ("flops" in k or "bytes" in k or "utilization" in k)
                       and "{" not in k}
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "code_bytes": int(ma.generated_code_size_in_bytes),
            }
        except Exception as e:  # pragma: no cover
            rec["memory"] = {"error": str(e)}

        txt = compiled.as_text()
        rec["collectives"] = RL.collective_bytes(txt,
                                                 loop_trip=cfg.n_blocks)
        rec["hlo_bytes"] = len(txt)
        from repro.launch.costs import analytic_cost
        dd, mm = mesh_split if mesh_split else (16, 16)
        rec["analytic"] = analytic_cost(
            cfg, shape, n_pods=2 if multi_pod else 1, data=dd, model=mm,
            mode=mode).as_dict()
        a = RL.analyze(rec)
        rec["roofline"] = {
            "compute_s": a.compute_s, "memory_s": a.memory_s,
            "collective_s": a.collective_s, "dominant": a.dominant,
            "useful_ratio": a.useful_ratio,
            "roofline_fraction": a.roofline_fraction,
        }
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def _done_cells(path: str):
    done = set()
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") in ("ok", "skip"):
                        done.add((r["arch"], r["shape"], r["mesh"],
                                  r.get("tag", "")))
                except json.JSONDecodeError:
                    pass
    return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default=None,
                    choices=[None, "dp", "tp", "fsdp_tp"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--mesh-split", default=None,
                    help="logical data,model split of the 256-chip pod, "
                         "e.g. 64,4")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (repeatable); python "
                         "literals, e.g. --set remat=False "
                         "--set remat_policy=save_ar")
    ap.add_argument("--out", default="experiments/dryrun.jsonl")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            import ast
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = _done_cells(args.out)
    archs = list_archs() if args.all or not args.arch else [args.arch]
    shape_names = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    for arch in archs:
        for shape_name in shape_names:
            for mp in meshes:
                key = (arch, shape_name, _mesh_name(mp), args.tag)
                if key in done:
                    print(f"[dryrun] SKIP (done) {key}")
                    continue
                print(f"[dryrun] {arch} × {shape_name} × {_mesh_name(mp)} …",
                      flush=True)
                split = (tuple(int(x) for x in args.mesh_split.split(","))
                         if args.mesh_split else None)
                rec = lower_cell(arch, shape_name, mp, mode=args.mode,
                                 extra_tag=args.tag, overrides=overrides,
                                 mesh_split=split)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                status = rec["status"]
                if status == "ok":
                    print(f"[dryrun]   memory_analysis: {rec['memory']}")
                    print(f"[dryrun]   cost_analysis:   {rec['cost']}")
                extra = (f" dominant={rec['roofline']['dominant']} "
                         f"frac={rec['roofline']['roofline_fraction']:.3f}"
                         if status == "ok" else rec.get("error", ""))
                print(f"[dryrun]   → {status} "
                      f"compile={rec.get('compile_s', 0):.1f}s {extra}",
                      flush=True)


if __name__ == "__main__":
    main()
