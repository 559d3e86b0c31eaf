"""Operations and bytes the served tokens need, from shapes alone.

These count the work the product needs, not the work an implementation
does: residue channels, padded lanes and idle slots are not counted, so a
change that removes them raises the share of the peak without making the
count stale.  What depends on the architecture comes from the
configuration's reference ``ref``: its ``linears(shape)`` and
``token_ops(shape, context)``; ``shape`` is its ``shape_of`` of the
configuration, which gives the hidden size ``d`` and the ``vocab``.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple


def head_ops(shape: Dict) -> int:
    """Operations of the tied output head for one token."""
    return 2 * shape["d"] * shape["vocab"]


def request_ops(ref, shape: Dict, prompt_len: int, new_tokens: int) -> int:
    """Operations one greedy request needs: the prompt's forward pass with
    the head at its last position, then one forward pass per further new
    token, each over the keys before it."""
    per_token = 2 * sum(k * n for k, n in ref.linears(shape))
    ops = prompt_len * per_token + head_ops(shape)
    ops += sum(ref.token_ops(shape, t + 1) for t in range(prompt_len))
    for j in range(1, new_tokens):
        ops += per_token + head_ops(shape)
        ops += ref.token_ops(shape, prompt_len + j)
    return ops


def served_ops(ref, shape: Dict, prompt_lens: Sequence[int],
               new_lens: Sequence[int]) -> int:
    return sum(request_ops(ref, shape, p, n)
               for p, n in zip(prompt_lens, new_lens))


def megakernel_work(ref, shape: Dict, counters: Dict) -> Dict[str, float]:
    """Logical int8 work of every projection routed through the megakernel.

    Rows are the real prompt tokens of each admission and one per emitting
    lane of each decode step.  Operations are 2·rows·K·N; bytes are the
    int8 weights once per forward pass (one per admission, one per decode
    step), the int8 activation rows in and the float32 rows out.
    """
    shapes = ref.linears(shape)
    rows = sum(counters["prompt_lens"]) \
        + counters["new_tokens"] - counters["requests"]
    passes = counters["requests"] + counters["chunks"] \
        * counters["decode_chunk"]
    w_bytes = sum(k * n for k, n in shapes)
    return {"ops": 2.0 * rows * w_bytes,
            "bytes": float(passes * w_bytes
                           + rows * sum(k + 4 * n for k, n in shapes)),
            "rows": float(rows), "passes": float(passes)}


def roofline_s(work: Dict[str, float], ops_per_s: float,
               bytes_per_s: float) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_bytes = work["ops"] / ops_per_s, work["bytes"] / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
