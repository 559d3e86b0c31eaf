"""The one traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) fixes the shape of one ``serve()``
call: how many requests it queues, the range and distribution of prompt
lengths and of new tokens, the per-slot token budget, and how many leading
prompt tokens all requests share.  The sizes are the distributions'
stratified quantiles, paired once and for all, so every seed and every
call carries the same set of requests; ``--seed`` and the call's index draw
the order of the queue, the token contents and each request's sampling
seed.  Where a call queues no more requests than the scheduler has slots,
every request is admitted at once, and the order moves requests between
slots without changing the work; where it queues more, the order also sets
which slot each later request refills, and so the lane-steps.

Keys of a mix file:

``requests_per_call``  requests queued at the start of each call;
``prompt_len``         ``{"dist": "uniform"|"log_uniform", "min", "max"}``;
``new_tokens``         the same form, for ``max_new_tokens``;
``slot_tokens``        each slot's logical capacity (prompt + new tokens);
``shared_prefix``      leading tokens every prompt of a call shares (0: none);
``source``             the public trace or dataset whose statistics the
                       sizes follow, and how they were cut;
``why``                one line: the users this traffic stands for.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
DISTS = ("uniform", "log_uniform")
_KEYS = {"requests_per_call", "prompt_len", "new_tokens", "slot_tokens",
         "shared_prefix", "source", "why"}

# fixed streams under the run seed, so that no two uses share random draws
_WEIGHTS, _CALLS, _WARMUP = 1, 2, 3


@dataclass(frozen=True)
class Req:
    """One request as the harness hands it to the scheduler."""
    prompt: List[int]
    max_new_tokens: int
    seed: int


def load(name: str, root: Path = BENCH) -> Dict:
    """Load and validate ``<root>/traffic/<name>.json``."""
    mix = json.loads((root / "traffic" / f"{name}.json").read_text())
    missing = _KEYS - set(mix)
    extra = set(mix) - _KEYS
    if missing or extra:
        raise ValueError(f"traffic {name}: missing keys {sorted(missing)}, "
                         f"unknown keys {sorted(extra)}")
    for key in ("prompt_len", "new_tokens"):
        d = mix[key]
        if d.get("dist") not in DISTS or not 1 <= d["min"] <= d["max"]:
            raise ValueError(f"traffic {name}: bad {key} {d}")
    if mix["prompt_len"]["max"] + mix["new_tokens"]["max"] \
            > mix["slot_tokens"]:
        raise ValueError(f"traffic {name}: the longest request does not fit "
                         "slot_tokens")
    if not 0 <= mix["shared_prefix"] < mix["prompt_len"]["min"]:
        raise ValueError(f"traffic {name}: shared_prefix must be shorter "
                         "than the shortest prompt")
    if mix["requests_per_call"] < 1:
        raise ValueError(f"traffic {name}: requests_per_call < 1")
    return mix


def quantiles(dist: Dict, n: int) -> List[int]:
    """The ``n`` stratified quantiles ``(i + 0.5) / n`` of ``dist``, as whole
    numbers inside ``[min, max]``."""
    lo, hi = dist["min"], dist["max"]
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        x = lo - 0.5 + u * (hi - lo + 1)
    else:
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    return [int(v) for v in np.clip(np.round(x), lo, hi)]


def sizes(mix: Dict) -> List[tuple]:
    """The (prompt length, new tokens) of the requests of every call: the
    quantiles of each distribution, paired by two fixed permutations."""
    n = mix["requests_per_call"]
    order = np.random.default_rng(0)
    plens = [quantiles(mix["prompt_len"], n)[i] for i in order.permutation(n)]
    news = [quantiles(mix["new_tokens"], n)[i] for i in order.permutation(n)]
    return list(zip(plens, news))


def _seed_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, *stream])


def weight_seed(seed: int) -> int:
    """The 31-bit seed the weights are made from (any ``--seed``)."""
    return int(_seed_rng(seed, _WEIGHTS).integers(2 ** 31 - 1))


def _requests(rng: np.random.Generator, shape: Sequence[tuple], vocab: int,
              shared_prefix: int) -> List[Req]:
    prefix = rng.integers(1, vocab, shared_prefix).tolist()
    out = []
    for plen, new in shape:
        body = rng.integers(1, vocab, plen - shared_prefix).tolist()
        out.append(Req(prompt=prefix + body, max_new_tokens=int(new),
                       seed=int(rng.integers(2 ** 31 - 1))))
    return out


def call(mix: Dict, seed: int, index: int, vocab: int) -> List[Req]:
    """The requests of the ``index``-th call of a run with ``seed``, in
    queue order: the mix's sizes in an order drawn from both."""
    rng = _seed_rng(seed, _CALLS, index)
    shape = sizes(mix)
    shape = [shape[i] for i in rng.permutation(len(shape))]
    return _requests(rng, shape, vocab, mix["shared_prefix"])


def warmup(mix: Dict, seed: int, vocab: int, bucket) -> List[Req]:
    """One request per prefill bucket the mix uses — its longest prompt —
    each with two new tokens, so the call runs every program a window call
    runs (prefill of that bucket, splice, first-token sample, one decode
    chunk).  ``bucket`` maps a prompt length to its prefill bucket.  Drawn
    from a stream of its own, apart from the window's calls."""
    longest: Dict[int, int] = {}
    for plen, _ in sizes(mix):
        b = bucket(plen)
        longest[b] = max(longest.get(b, 0), plen)
    shape = [(plen, 2) for _, plen in sorted(longest.items())]
    return _requests(_seed_rng(seed, _WARMUP), shape, vocab,
                     mix["shared_prefix"])
