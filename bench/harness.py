"""The benchmark harness: one cell, one seed, one process.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``   the model as published, the program
  config it runs as, the serving deployment and the control's precision;
  optionally ``"reference": "<module>"``, the file ``bench/<module>.py``
  (without it, the Llama configurations' reference, `reference_of`), and
  ``"program_keys"``, published keys beyond the nine Llama ones, each
  with the ModelConfig field it sets;
* ``bench/<module>.py``             the configuration's plain reference;
* ``bench/traffic/<mix>.json``      read by the one generator, `traffic`;
* ``bench/metrics/<metric>.py``     one reader, ``read(ctx)``, per metric;
* ``bench/limits/<cell>.json``      the correctness limit of a cell;
* ``bench/peaks.json``              the chip's peaks, by ``device_kind``.

A run builds the weights from the seed on the device, builds the program's
`SlotScheduler`, warms the cell's own programs with one small call, then
runs whole ``serve()`` calls back to back until ``seconds`` have passed.
After the window it reads the device's memory peak, frees the program and
checks a sample of the served tokens against the configuration's plain
reference.  With ``trace=True`` the first call of the window runs under the
profiler and the per-layer metrics are read from it.

A reference module imports nothing of the program and provides:

* ``shape_of(model)``: from the configuration file's published keys, a
  dict of hashable values, among them the hidden size ``d`` and the
  ``vocab`` (`costs.head_ops` reads those);
* ``make_weights(model, weight_seed, dtype)``: its own weights, made from
  the run's weight seed by the recipe the program follows;
* ``logits(W, tokens, shape, lower=None)``: (B, S, vocab) float32 logits of
  every position; ``shape`` is ``tuple(sorted(shape_of(model).items()))``
  and ``lower`` the configuration's ``control`` as `control_lower` gives
  it;
* ``gaps(W, tokens, served, shape, lower=None)``: per position, the gap by
  which the served token's logit (with ``lower``, the logit of the token
  the lowered control puts first) lies below the reference's best, over
  `pack`'s arrays;
* ``linears(shape)``: the (K, N) of every projection in one token's
  forward pass, over all layers, that the configuration's linear path
  runs;
* ``token_ops(shape, context)``: one token's operations beyond the
  projections and the head: attention over ``context`` keys in the layers
  that attend, and any per-token mixer work, such as a state update.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace as tracelib
from bench import traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# published (Hugging Face) key → the program's ModelConfig field, for every
# configuration; a configuration's ``program_keys`` add to these
_PROGRAM_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
                 "num_attention_heads": "num_heads",
                 "num_key_value_heads": "num_kv_heads",
                 "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                 "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
                 "tie_word_embeddings": "tie_embeddings"}

SPAN_CALL = "bench.serve_call"
SPAN_BETWEEN = "bench.make_requests"


class BenchError(Exception):
    """The run cannot measure: no chip, an unknown chip, a bad file."""


# ---------------------------------------------------------------- files --
def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve_cell(bench: Dict, workload: str, root: Path = ROOT) -> Dict:
    """The cell's entry, its configuration file, its traffic mix, its
    limits and its configuration's reference module."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    return {"cell": cell, "config": config,
            "reference": reference_of(config, root),
            "mix": traffic.load(cell["traffic"], root / "bench"),
            "limits": json.loads((root / "bench" / "limits"
                                  / f"{workload}.json").read_text())}


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return _load(root / "bench" / "metrics" / f"{name}.py",
                 f"bench_metric_{name}").read


def reference_of(config: Dict, root: Path = ROOT):
    """The module ``bench/<reference>.py`` that the configuration names,
    ``bench/reference.py`` where it names none."""
    name = config.get("reference", "reference")
    path = root / "bench" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"the configuration's reference bench/{name}.py "
                         f"is not there")
    return _load(path, f"reference_{name}")


def per_layer_for(bench: Dict, workload: str) -> List[Dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and ("workloads" in m or m["moves"] in e2e)]


def end_to_end_for(bench: Dict, workload: str) -> List[Dict]:
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]


def peaks_for(kind: str, root: Path = ROOT) -> Dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["chips"]:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (have {sorted(table['chips'])})")
    return table["chips"][kind]


def program_config(config: Dict):
    """The program's ModelConfig: its registered config, with every
    published size the file gives: the nine Llama keys, then those its
    ``program_keys`` map to a field."""
    from repro.configs.base import ModelConfig, get_config

    if config.get("hidden_act", "silu") != "silu":
        raise BenchError("the program's block applies silu only")
    over = {_PROGRAM_KEYS[k]: config[k] for k in _PROGRAM_KEYS}
    over["head_dim"] = config.get("head_dim", config["hidden_size"]
                                  // config["num_attention_heads"])
    over["param_dtype"] = config["serving"]["dtype"]
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    for key, field in config.get("program_keys", {}).items():
        if field not in fields:
            raise BenchError(f"program_keys maps {key!r} to {field!r}, "
                             f"which ModelConfig has not")
        if key not in config:
            raise BenchError(f"program_keys names {key!r}, which the "
                             f"configuration does not give")
        over[field] = config[key]
    return dataclasses.replace(get_config(config["program"]), **over)


# ------------------------------------------------------------- device --
def device_info(cell: Dict, require_tpu: bool = True) -> Dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU (first device is "
                         f"{info['platform']})")
    if info["count"] < cell["chips"]:
        raise BenchError(f"the cell asks for {cell['chips']} chips, JAX "
                         f"sees {info['count']}")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    keeps no allocator statistics, as the CPU does)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts programs lowered (then compiled or read from the persistent
    cache) while ``active``: a new shape inside the window shows here."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self._event = "/jax/core/compile/jaxpr_to_mlir_module_duration"
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.active and event == self._event:
            self.count += 1


class GcClock:
    """Times the collections of Python's garbage collector while
    ``active``: a host pause inside the window shows here."""

    def __init__(self):
        self.active = False
        self.count = 0
        self.seconds = 0.0
        self.longest = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, _info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            took = time.perf_counter() - self._t
            self._t = None
            if self.active:
                self.count += 1
                self.seconds += took
                self.longest = max(self.longest, took)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


# -------------------------------------------------------------- build --
def program_requests(reqs: List[traffic.Req]):
    """The generator's requests as the program's `serve.Request`s."""
    from repro.serve import Request

    return [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                    seed=r.seed) for r in reqs]


def build(config: Dict, mix: Dict, seed: int):
    """Weights from the seed in one jitted call on the device, then the
    program's scheduler.  Only the scheduler keeps the weights."""
    import jax

    from repro.models import transformer as T
    from repro.serve import SlotScheduler

    cfg = program_config(config)
    serving = config["serving"]
    key = jax.random.PRNGKey(traffic.weight_seed(seed))
    params = jax.jit(lambda k: T.make_params(cfg, k))(key)
    jax.block_until_ready(params)
    sched = SlotScheduler(cfg, params, slots=serving["slots"],
                          block_size=serving["block_size"],
                          slot_tokens=mix["slot_tokens"],
                          decode_chunk=serving["decode_chunk"],
                          temperature=0.0, eos_id=None,
                          prefix_sharing=serving["prefix_sharing"])
    del params
    return sched


def warm(sched, mix: Dict, seed: int) -> None:
    """One small call that runs every program the window's calls run."""
    from repro.serve.engine import bucket_plen

    reqs = traffic.warmup(mix, seed, sched.cfg.vocab_size,
                          lambda n: bucket_plen(sched.cfg, n))
    sched.serve(program_requests(reqs))


# ------------------------------------------------------------- window --
@dataclasses.dataclass
class Call:
    reqs: List[traffic.Req]
    outs: List[List[int]]
    stats: Dict
    seconds: float


def window(sched, mix: Dict, seed: int, seconds: float,
           profile_dir: Optional[str] = None) -> Dict:
    """Whole ``serve()`` calls back to back until ``seconds`` have passed.
    With ``profile_dir`` the first call runs under the profiler."""
    import jax

    calls: List[Call] = []
    t0 = time.perf_counter()
    while True:
        traced = profile_dir is not None and not calls
        if traced:
            jax.profiler.start_trace(profile_dir)
        with jax.profiler.TraceAnnotation(SPAN_BETWEEN):
            reqs = traffic.call(mix, seed, len(calls), sched.cfg.vocab_size)
            prog = program_requests(reqs)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_CALL):
            outs = sched.serve(prog)
        calls.append(Call(reqs, outs, dict(sched.stats),
                          time.perf_counter() - t))
        if traced:
            jax.profiler.stop_trace()
        if time.perf_counter() - t0 >= seconds:
            break
    return {"calls": calls, "seconds": time.perf_counter() - t0}


def count_failed(calls: List[Call], vocab: int) -> int:
    """Requests that did not come back whole: prompt echoed, exactly
    ``max_new_tokens`` new tokens, every one in the vocabulary."""
    failed = 0
    for c in calls:
        for r, out in zip(c.reqs, c.outs):
            new = out[len(r.prompt):]
            if (out[:len(r.prompt)] != r.prompt
                    or len(new) != r.max_new_tokens
                    or not all(0 <= t < vocab for t in new)):
                failed += 1
    return failed


def sample(calls: List[Call], seed: int, k: int) -> List[tuple]:
    """``k`` finished requests drawn from the seed over every call of the
    window, the longest of one call among them: (prompt, new tokens)
    pairs."""
    rng = np.random.default_rng([int(seed) % 2 ** 64, 4])
    first = int(rng.integers(len(calls)))
    reqs = calls[first].reqs
    longest = (first, max(range(len(reqs)),
                          key=lambda i: reqs[i].max_new_tokens))
    others = [(c, i) for c, call in enumerate(calls)
              for i in range(len(call.reqs)) if (c, i) != longest]
    drawn = rng.choice(len(others), size=min(k - 1, len(others)),
                       replace=False)
    out = []
    for c, i in [longest] + [others[int(j)] for j in drawn]:
        prompt = calls[c].reqs[i].prompt
        out.append((prompt, calls[c].outs[i][len(prompt):]))
    return out


def pack(sequences: Sequence[Tuple[Sequence[int], Sequence[int]]],
         length: int):
    """(tokens, served) arrays for a reference's ``gaps`` from (prompt, new
    tokens) pairs, right-padded to ``length``."""
    tokens = np.zeros((len(sequences), length), np.int32)
    served = np.full((len(sequences), length), -1, np.int32)
    for i, (prompt, new) in enumerate(sequences):
        seq = list(prompt) + list(new)
        if len(seq) > length:
            raise ValueError(f"sequence of {len(seq)} tokens > {length}")
        tokens[i, :len(seq)] = seq
        served[i, len(prompt) - 1:len(seq) - 1] = new
    return tokens, served


def gap_readings(ref, config: Dict, seed: int, seqs: List[tuple],
                 length: int, lower=None) -> Dict[str, List[float]]:
    """Per sampled sequence, the widest gap of a served token's logit in
    the reference ``ref`` below the reference's best, the sum of the gaps
    and the number of served tokens.  With ``lower`` the gaps are those of
    the token the lowered control puts first."""
    import jax

    W = ref.make_weights(config, traffic.weight_seed(seed),
                         config["serving"]["dtype"])
    tokens, served = pack(seqs, length)
    shape = tuple(sorted(ref.shape_of(config).items()))
    g = np.asarray(jax.device_get(
        ref.gaps(W, tokens, served, shape, lower)), np.float64)
    return {"max": [float(v) for v in g.max(axis=1)],
            "sum": [float(v) for v in g.sum(axis=1)],
            "count": [int(v) for v in (served >= 0).sum(axis=1)]}


def numbers(readings: Dict[str, List[float]]) -> Dict[str, float]:
    """The numbers a cell may compare: the widest gap over every served
    token of the sample, and the mean gap per served token."""
    return {"max_logit_gap": max(readings["max"]),
            "mean_logit_gap": sum(readings["sum"]) / sum(readings["count"])}


def control_lower(config: Dict):
    c = config["control"]
    return (int(c["bits"]), tuple(c["matmuls"]))


def check(ref, config: Dict, limits: Dict, seed: int, calls: List[Call],
          slot_tokens: int, lower=None) -> Dict:
    """The numbers compared against the reference ``ref``, each with its
    value and its limit: those the cell's limits file names, and the count
    of requests that failed.  With ``lower`` the control stands in the
    program's place."""
    seqs = sample(calls, seed, limits["sample_requests"])
    got = numbers(gap_readings(ref, config, seed, seqs, slot_tokens,
                               lower))
    out = {name: {"value": value, "limit": limits[name]["limit"]}
           for name, value in got.items() if name in limits}
    out["failed_requests"] = {"value": count_failed(
        calls, config["vocab_size"]), "limit": 0}
    return out


def passed(checked: Dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())


# ------------------------------------------------------------ metrics --
def call_counters(c: Call, serving: Dict) -> Dict:
    """Counters of one call, from the scheduler's stats and the traffic."""
    return {"requests": c.stats["requests"],
            "new_tokens": c.stats["new_tokens"],
            "chunks": c.stats["chunks"],
            "decode_chunk": serving["decode_chunk"],
            "slots": serving["slots"],
            "prompt_lens": [len(r.prompt) for r in c.reqs],
            "new_lens": [r.max_new_tokens for r in c.reqs]}


def trace_context(tr: Dict, call: Call, config: Dict, ref,
                  peaks: Dict) -> Dict:
    """What a per-layer reader reads: the traced call's trace ``tr`` and
    window, its counters, the configuration, its reference module
    (``reference``) and that reference's shape of it (``model``)."""
    win = tracelib.window_of(tr, SPAN_CALL)
    return {"trace": tr, "window": win,
            "window_s": (win[1] - win[0]) * 1e-9,
            "busy_s": tracelib.busy_ns(tr, win) * 1e-9,
            "counters": call_counters(call, config["serving"]),
            "reference": ref, "model": ref.shape_of(config),
            "config": config, "peaks": peaks}


def read_per_layer(bench: Dict, workload: str, ctx: Dict,
                   root: Path = ROOT, log=None) -> Dict:
    """The cell's per-layer metrics.  A reader that finds nothing to read
    leaves its metric out of the line, and the run says so on stderr: in a
    cell that lists the metric, a kernel or program missing from the trace
    under the name the reader matches."""
    out = {}
    for m in per_layer_for(bench, workload):
        v = metric_reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        elif log:
            log(f"per_layer {m['name']} read nothing in the trace: left out")
    return out


# ---------------------------------------------------------------- run --
def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: Path = ROOT, require_tpu: bool = True,
        log=None) -> Dict:
    """One run of one cell; returns the result line's object."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    bench = load_benchmark(root)
    spec = resolve_cell(bench, workload, root)
    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    ref = spec["reference"]
    device = device_info(cell, require_tpu)
    peaks = peaks_for(device["kind"], root)
    counter = CompileCounter()

    sched = build(config, mix, seed)
    warm(sched, mix, seed)
    # set-up's garbage is collected in set-up, and what set-up leaves is
    # kept out of the window's collections
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}")

    profile_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    gc_clock = GcClock()
    try:
        counter.active = gc_clock.active = True
        win = window(sched, mix, seed, seconds, profile_dir)
        counter.active = gc_clock.active = False
        calls = win["calls"]
        new_tokens = sum(c.stats["new_tokens"] for c in calls)
        log(f"window calls {len(calls)} seconds {win['seconds']:.3f} "
            f"new_tokens {new_tokens} per_call_s "
            f"{[round(c.seconds, 3) for c in calls]}")
        log(f"compiles_in_window {counter.count}")
        log(f"gc_in_window collections {gc_clock.count} seconds "
            f"{gc_clock.seconds:.3f} longest {gc_clock.longest:.3f}")
        peak = memory_peak_bytes()
        device["memory_peak_bytes"] = peak
        gc.unfreeze()
        del sched
        gc.collect()

        result = {"correct": False, "attempted": sum(len(c.reqs)
                                                     for c in calls),
                  "failed": count_failed(calls, config["vocab_size"])}
        if trace:
            ctx = trace_context(tracelib.load(profile_dir), calls[0],
                                config, ref, peaks)
            metrics = read_per_layer(bench, workload, ctx, root, log)
            device["busy_s"] = ctx["busy_s"]
            device["window_s"] = ctx["window_s"]
            result["breakdown"] = tracelib.breakdown(ctx["trace"],
                                                     ctx["window"])
        else:
            values = {"tok_s": new_tokens / win["seconds"],
                      "hbm_peak_gb": peak / 1e9, "setup_s": setup_s}
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in end_to_end_for(bench, workload)}
    finally:
        gc_clock.close()
        gc.unfreeze()
        if profile_dir:
            shutil.rmtree(profile_dir, ignore_errors=True)

    checked = check(ref, config, spec["limits"], seed, calls,
                    mix["slot_tokens"])
    result.update(correct=passed(checked), metrics=metrics, device=device,
                  compiles_in_window=counter.count, check=checked)
    return result
