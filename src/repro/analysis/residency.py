"""Residency/purity pass — structural invariants of closed jaxprs.

The tests used to carry three hand-rolled jaxpr spies (test_chain's
rem-outside-pallas walker, test_serve's callback/scan primitive collector,
test_kernels' ``str(jaxpr).count("pallas_call")``).  This pass generalizes
them into one traversal: :func:`summarize` walks a closed jaxpr through
every sub-jaxpr-carrying param (scan/cond/while/pjit/custom_*/pallas_call),
tracking whether it is inside a ``pallas_call`` body, and returns a
:class:`JaxprSummary` with primitive counts split by residency.  The check_*
helpers turn a summary into :class:`~repro.analysis.findings.Report`
findings with the invariant named — "zero standalone conversions", "single
fused kernel", "no host callbacks in the decode scan" (DESIGN.md §16).
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Dict, Iterable, Optional

from .findings import Report

__all__ = [
    "JaxprSummary", "summarize", "summarize_fn",
    "check_resident", "check_pallas_count", "check_no_callbacks",
    "check_reduced_wire", "collective_family", "MODULAR_PRIMS",
    "COLLECTIVE_PRIMS",
]

# Primitives that perform a modular reduction outside a kernel body — on a
# resident path every one of these must live inside pallas_call.
MODULAR_PRIMS = ("rem", "mod")

# Cross-device collective FAMILIES (repro.dist's sharded launches).  JAX
# respells these between releases and rewrites (psum → psum2 →
# psum_invariant, all_gather → all_gather_invariant, psum_scatter), so the
# walk matches by family and records every site under the family name,
# with its operand shapes/dtypes — the wire checks and
# `dist.comms.collective_wire_bytes` reason about WHAT crosses the
# interconnect, not just that something does.
COLLECTIVE_PRIMS = ("psum", "all_gather", "all_to_all", "ppermute",
                    "reduce_scatter", "pmax", "pmin")

# Host-boundary primitive families: io/pure/debug callbacks, debug_print,
# host outside-calls and in/outfeed.
_CALLBACK_MARKERS = ("callback", "debug_print", "outside_call", "infeed",
                     "outfeed")


def collective_family(name: str) -> Optional[str]:
    """The collective family a primitive name belongs to, or None."""
    if name == "psum_scatter":
        return "reduce_scatter"
    for fam in COLLECTIVE_PRIMS:
        if re.fullmatch(rf"{fam}\d*(_invariant)?", name):
            return fam
    return None


def _is_callback(name: str) -> bool:
    return any(marker in name for marker in _CALLBACK_MARKERS)


@dataclasses.dataclass
class JaxprSummary:
    """Primitive census of a closed jaxpr, split by kernel residency."""

    outside: Counter            # primitive name -> count outside pallas_call
    inside: Counter             # primitive name -> count inside kernel bodies
    pallas_calls: int           # number of pallas_call launch sites
    # one entry per collective site outside kernel bodies:
    # (primitive name, ((operand shape, operand dtype str), ...))
    collectives: list = dataclasses.field(default_factory=list)

    @property
    def all_prims(self) -> Counter:
        return self.outside + self.inside

    def count_outside(self, names: Iterable[str]) -> int:
        return sum(self.outside.get(n, 0) for n in names)

    @property
    def callbacks(self) -> int:
        return sum(c for n, c in self.all_prims.items() if _is_callback(n))

    @property
    def scans(self) -> int:
        return self.outside.get("scan", 0)


def _sub_jaxprs(eqn):
    """Yield every (Closed)Jaxpr hiding in an eqn's params."""
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            core = getattr(j, "jaxpr", None)
            if core is not None:                    # ClosedJaxpr
                yield core if hasattr(core, "eqns") else j
            elif hasattr(j, "eqns"):                # raw Jaxpr
                yield j


def summarize(closed_jaxpr) -> JaxprSummary:
    """Walk a ClosedJaxpr (or raw Jaxpr) and census its primitives."""
    summary = JaxprSummary(outside=Counter(), inside=Counter(),
                           pallas_calls=0)
    root = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def walk(jx, inside_pallas: bool) -> None:
        for eqn in jx.eqns:
            nm = eqn.primitive.name
            if nm == "pallas_call":
                summary.pallas_calls += 1
            fam = collective_family(nm)
            (summary.inside if inside_pallas else summary.outside)[
                fam or nm] += 1
            if not inside_pallas and fam is not None:
                summary.collectives.append((fam, tuple(
                    (tuple(v.aval.shape), str(v.aval.dtype))
                    for v in eqn.invars if hasattr(v.aval, "shape"))))
            inner = inside_pallas or nm == "pallas_call"
            for sub in _sub_jaxprs(eqn):
                walk(sub, inner)

    walk(root, False)
    return summary


def summarize_fn(fn, *example_args, **example_kwargs) -> JaxprSummary:
    """Trace ``fn`` on example args and summarize the resulting jaxpr."""
    import jax

    return summarize(jax.make_jaxpr(fn)(*example_args, **example_kwargs))


# ----------------------------------------------------------------- checks --
def check_resident(summary: JaxprSummary, *,
                   min_pallas_calls: int = 1,
                   subject: str = "jaxpr") -> Report:
    """Resident-path invariant: every modular reduction lives in a kernel.

    Errors when any ``rem``/``mod`` primitive sits outside ``pallas_call``
    (a standalone conversion escaped fusion) or when no kernel is present at
    all (the "resident" trace never reached Pallas, so the invariant would
    hold vacuously).
    """
    rep = Report(subject=f"residency:{subject}")
    stray = summary.count_outside(MODULAR_PRIMS)
    if stray:
        per = {n: summary.outside[n] for n in MODULAR_PRIMS
               if summary.outside.get(n)}
        rep.add("residency", "resident path",
                f"{stray} modular-reduction primitive(s) outside "
                f"pallas_call ({per}) — a standalone conversion escaped "
                f"the fused kernel")
    if summary.pallas_calls < min_pallas_calls:
        rep.add("residency", "resident path",
                f"only {summary.pallas_calls} pallas_call(s) in the jaxpr "
                f"(expected >= {min_pallas_calls}) — the resident invariant "
                f"would hold vacuously")
    return rep


def check_pallas_count(summary: JaxprSummary, expected: int, *,
                       subject: str = "jaxpr") -> Report:
    """Fused-launch invariant: exactly N ``pallas_call`` sites."""
    rep = Report(subject=f"residency:{subject}")
    if summary.pallas_calls != expected:
        rep.add("residency", "kernel launches",
                f"{summary.pallas_calls} pallas_call(s) in the jaxpr, "
                f"expected exactly {expected} — fusion split or duplicated "
                f"a launch")
    return rep


def check_no_callbacks(summary: JaxprSummary, *,
                       require_scan: bool = False,
                       max_scans: Optional[int] = None,
                       subject: str = "jaxpr") -> Report:
    """Decode-scan invariant: no host round-trips inside the computation."""
    rep = Report(subject=f"residency:{subject}")
    bad: Dict[str, int] = {
        n: c for n, c in summary.all_prims.items() if _is_callback(n)}
    if bad:
        rep.add("residency", "host boundary",
                f"host callback primitive(s) in the jaxpr: {bad} — tokens "
                f"must cross to the host once, after the scan")
    if require_scan and summary.scans == 0:
        rep.add("residency", "decode loop",
                "no lax.scan in the jaxpr — the decode loop was unrolled "
                "or runs on the host")
    if max_scans is not None and summary.scans > max_scans:
        rep.add("residency", "decode loop",
                f"{summary.scans} lax.scan(s) in the jaxpr, expected at "
                f"most {max_scans} — the decode loop was split")
    return rep


def check_reduced_wire(summary: JaxprSummary, channels: Iterable[int], *,
                       nlimbs: Optional[Iterable[int]] = None,
                       subject: str = "jaxpr") -> Report:
    """Channel-sharded wire invariant: residues never cross the interconnect.

    The C-sharded megakernel's contract (DESIGN.md §17) is that the ONLY
    thing a launch communicates is its post-MRC reduced result — the narrow
    (L1, M, N) int32 CRT-partial limb planes, or a plain float output.  A
    collective whose operand is an integer (C, M, N) stack with C equal to a
    launch basis' channel count means a residue slab is on the wire — the
    partitioning leaked pre-reduction state.  ``channels`` names the channel
    counts of the model's launch bases; ``nlimbs`` whitelists the limb-plane
    leading dims (a basis whose L1 collides with another basis' C would
    otherwise false-positive).
    """
    rep = Report(subject=f"residency:{subject}")
    chans = set(int(c) for c in channels)
    limbs = set(int(v) for v in (nlimbs or ()))
    for name, operands in summary.collectives:
        for shape, dtype in operands:
            if (len(shape) >= 3 and shape[0] in chans
                    and shape[0] not in limbs
                    and "int" in dtype and "uint" not in dtype[:4]):
                rep.add("residency", "reduced wire",
                        f"collective '{name}' moves an integer {shape} "
                        f"{dtype} stack whose leading dim matches a launch "
                        f"basis' channel count — residues crossed the "
                        f"interconnect instead of the post-MRC reduced "
                        f"result")
    return rep
