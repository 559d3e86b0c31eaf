"""Interval abstract interpretation over jaxprs — overflow proofs on traces.

The config-level checker (`analysis.bounds`) proves the pipeline *as
designed*; this pass proves the pipeline *as traced*: it walks a closed
jaxpr propagating exact integer intervals (`analysis.intervals`) through the
primitives the RNS datapath actually emits — ring ops, ``dot_general``
(contraction depth read off the operand shapes), floored ``rem``, the fold
ladder's shift/mask/multiply-add rungs, clamps, selects, reductions and the
structural primitives — and flags every integer-dtype intermediate whose
derived range escapes its dtype.  Because constants (the moduli table, the
rung schedule, the MRC inverse table) enter the jaxpr as literals/consts,
their intervals are read from the actual values, so the proof covers the
real channel set of the trace, not a model of it.

Soundness discipline: an unknown primitive (or a loop carry) maps to ⊤ and
everything derived from it is *unproven*, reported once as a warning — the
pass never silently assumes a range.  ``pallas_call`` bodies are NOT entered
(kernel refs live outside this domain); the in-kernel bound story is the
config-level checker's job (DESIGN.md §16).

Entry points: :func:`check_fn_bounds` traces a callable and checks it;
:func:`interpret` walks an existing ``ClosedJaxpr``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .findings import Report
from .intervals import TOP, Interval, dtype_range

__all__ = ["check_fn_bounds", "interpret", "AbsintResult"]


@dataclasses.dataclass
class AbsintResult:
    report: Report
    out_intervals: List[Interval]
    unproven: int                 # eqns whose outputs left the domain


def _is_int(aval) -> bool:
    return dtype_range(getattr(aval, "dtype", None)) is not None


def _const_interval(val) -> Interval:
    """Interval of a literal/constvar from its concrete value."""
    arr = np.asarray(val)
    if arr.dtype.kind not in "iu" or arr.size == 0:
        return TOP
    return Interval(int(arr.min()), int(arr.max()))


def _contraction_depth(eqn) -> int:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    shape = eqn.invars[0].aval.shape
    k = 1
    for d in lhs_c:
        k *= shape[d]
    return k


def _reduced_size(eqn) -> int:
    axes = eqn.params.get("axes", ())
    shape = eqn.invars[0].aval.shape
    n = 1
    for a in axes:
        n *= shape[a]
    return n


class _Interp:
    def __init__(self, report: Report):
        self.report = report
        self.unproven = 0
        self._warned: set = set()

    # -------------------------------------------------------------- driver -
    def run(self, jaxpr, consts, in_ivs: Sequence[Interval]
            ) -> List[Interval]:
        env: Dict[Any, Interval] = {}

        def read(atom) -> Interval:
            if hasattr(atom, "val"):                       # Literal
                return _const_interval(atom.val)
            return env.get(atom, TOP)

        def write(var, iv: Interval) -> None:
            env[var] = iv

        for cv, c in zip(jaxpr.constvars, consts):
            write(cv, _const_interval(c))
        for v, iv in zip(jaxpr.invars, in_ivs):
            write(v, iv)
        for eqn in jaxpr.eqns:
            ins = [read(a) for a in eqn.invars]
            outs = self.eqn_intervals(eqn, ins)
            for var, iv in zip(eqn.outvars, outs):
                iv = self._check_dtype(eqn, var, iv)
                write(var, iv)
        return [read(v) for v in jaxpr.outvars]

    def _check_dtype(self, eqn, var, iv: Interval) -> Interval:
        rng = dtype_range(getattr(var.aval, "dtype", None))
        if rng is None:
            return iv
        if iv.lo is None or iv.hi is None:
            self.unproven += 1
            return iv
        assert rng.lo is not None and rng.hi is not None
        if iv.lo < rng.lo or iv.hi > rng.hi:
            self.report.add(
                "absint", f"'{eqn.primitive.name}'",
                f"possible {var.aval.dtype} overflow: derived range "
                f"{iv} escapes {rng}")
            # the concrete machine wraps: everything downstream is unknown
            return rng
        return iv

    def _warn_once(self, key: str, msg: str) -> None:
        if key not in self._warned:
            self._warned.add(key)
            self.report.add("absint", key, msg, severity="warning")

    # ------------------------------------------------------ primitive rules -
    def eqn_intervals(self, eqn, ins: List[Interval]) -> List[Interval]:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        def uni(iv: Interval) -> List[Interval]:
            return [iv] * n_out

        if name in ("add", "add_any"):
            return uni(ins[0] + ins[1])
        if name == "sub":
            return uni(ins[0] - ins[1])
        if name == "mul":
            return uni(ins[0] * ins[1])
        if name == "neg":
            return uni(-ins[0])
        if name == "abs":
            return uni(ins[0].abs())
        if name in ("max", "min"):
            a, b = ins[0], ins[1]
            if (a.lo is None or a.hi is None or b.lo is None
                    or b.hi is None):
                return uni(TOP)
            pick = max if name == "max" else min
            return uni(Interval(pick(a.lo, b.lo), pick(a.hi, b.hi)))
        if name == "rem":
            n, d = ins[0], ins[1]
            if d.lo is not None and d.hi is not None and d.lo > 0:
                hi = d.hi - 1
                if n.lo is not None and n.hi is not None and n.lo >= 0:
                    return uni(Interval(0, min(hi, n.hi)))
                return uni(Interval(-hi, hi))
            return uni(TOP)
        if name == "dot_general":
            return uni(ins[0].dot(ins[1], _contraction_depth(eqn)))
        if name == "reduce_sum":
            return uni(ins[0] * Interval.point(_reduced_size(eqn)))
        if name in ("reduce_max", "reduce_min", "cumsum"):
            if name == "cumsum" and not ins[0].is_top:
                n_ax = eqn.invars[0].aval.shape[eqn.params.get("axis", 0)]
                return uni(ins[0] * Interval.point(n_ax))
            return uni(ins[0])
        if name == "clamp":
            lo, x, hi = ins
            if lo.lo is None or hi.hi is None:
                return uni(x)
            return uni(x.clip(lo.lo, hi.hi))
        if name == "select_n":
            out = ins[1]
            for case in ins[2:]:
                out = out.union(case)
            return uni(out)
        if name == "convert_element_type":
            return uni(ins[0])        # _check_dtype flags narrowing escapes
        if name in ("broadcast_in_dim", "reshape", "transpose", "squeeze",
                    "expand_dims", "slice", "dynamic_slice", "rev", "copy",
                    "stop_gradient", "device_put", "gather", "tie_in"):
            return uni(ins[0])
        if name == "concatenate":
            out = ins[0]
            for o in ins[1:]:
                out = out.union(o)
            return uni(out)
        if name == "pad":
            return uni(ins[0].union(ins[1]))
        if name == "iota":
            dim = eqn.params["dimension"]
            return uni(Interval(0, max(eqn.params["shape"][dim] - 1, 0)))
        if name in ("shift_right_logical", "shift_right_arithmetic"):
            v, s = ins[0], ins[1]
            if (s.lo is not None and s.lo == s.hi and v.lo is not None
                    and v.lo >= 0):
                return uni(v.rshift(s.lo))
            return uni(TOP)
        if name == "shift_left":
            s = ins[1]
            if s.lo is not None and s.lo == s.hi:
                return uni(ins[0] * Interval.point(1 << s.lo))
            return uni(TOP)
        if name == "and":
            a, b = ins
            if (a.lo is not None and a.hi is not None and b.lo is not None
                    and b.hi is not None and a.lo >= 0 and b.lo >= 0):
                return uni(Interval(0, min(a.hi, b.hi)))
            return uni(TOP)
        if name in ("or", "xor"):
            a, b = ins
            if (a.lo is not None and a.hi is not None and b.lo is not None
                    and b.hi is not None and a.lo >= 0 and b.lo >= 0):
                bits = max(a.hi, b.hi).bit_length()
                return uni(Interval(0, (1 << bits) - 1))
            return uni(TOP)
        if name in ("eq", "ne", "lt", "le", "gt", "ge", "is_finite"):
            return uni(Interval(0, 1))
        sub = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
               or eqn.params.get("fun_jaxpr"))
        if (name not in ("scan", "while", "cond", "pallas_call")
                and (hasattr(sub, "jaxpr") or hasattr(sub, "eqns"))):
            # Any call-like eqn (pjit/jit, closed_call, checkpoint,
            # custom_jvp/vjp, …) is entered by its sub-jaxpr, not by name:
            # JAX renames these between releases (pjit → jit in 0.9).
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            outs = self.run(inner, getattr(sub, "consts", ()),
                            ins[len(ins) - len(inner.invars):]
                            if len(inner.invars) <= len(ins) else
                            [TOP] * len(inner.invars))
            return outs if len(outs) == n_out else uni(TOP)
        if name in ("scan", "while", "cond"):
            # Loop carries would need a fixpoint; analyze the body once with
            # ⊤ carries so in-body constants still get checked, but treat the
            # outputs as unknown.
            self._warn_once(name, "loop analyzed with ⊤ carries — body "
                            "checked, outputs unproven")
            subs = []
            if "jaxpr" in eqn.params:
                subs.append(eqn.params["jaxpr"])
            subs.extend(eqn.params.get("branches", ()))
            for p in ("cond_jaxpr", "body_jaxpr"):
                if p in eqn.params:
                    subs.append(eqn.params[p])
            for sub in subs:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                self.run(inner, getattr(sub, "consts", ()),
                         [TOP] * len(inner.invars))
            return uni(TOP)
        if name == "pallas_call":
            # Kernel bodies live outside this domain (Refs, grid semantics);
            # the config-level bound checker owns the in-kernel proof.
            self._warn_once("pallas_call", "kernel bodies are proven by the "
                            "config-level bound pass, not entered here")
            return uni(TOP)
        self._warn_once(name, f"no interval rule for primitive '{name}' — "
                        f"its outputs are unproven")
        return uni(TOP)


def interpret(closed_jaxpr, in_intervals: Sequence[Interval], *,
              subject: str = "jaxpr") -> AbsintResult:
    """Walk a ``ClosedJaxpr`` with the given input intervals."""
    rep = Report(subject=f"absint:{subject}")
    interp = _Interp(rep)
    outs = interp.run(closed_jaxpr.jaxpr, closed_jaxpr.consts, in_intervals)
    return AbsintResult(report=rep, out_intervals=outs,
                        unproven=interp.unproven)


def check_fn_bounds(fn, *example_args,
                    bounds: Optional[Sequence[Optional[Tuple[int, int]]]]
                    = None, subject: str = "fn") -> AbsintResult:
    """Trace ``fn`` on example args and interval-check the jaxpr.

    ``bounds`` gives (lo, hi) per *flattened* argument leaf; ``None`` entries
    (and a ``None`` bounds) default to the leaf dtype's full range for
    integer leaves — e.g. int8 operands start at [−128, 127], exactly the
    external-operand contract — and ⊤ for floats.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*example_args)
    leaves = jax.tree_util.tree_leaves(example_args)
    ivs: List[Interval] = []
    for i, leaf in enumerate(leaves):
        b = bounds[i] if bounds is not None and i < len(bounds) else None
        if b is not None:
            ivs.append(Interval(int(b[0]), int(b[1])))
        else:
            rng = dtype_range(getattr(leaf, "dtype", None))
            ivs.append(rng if rng is not None else TOP)
    return interpret(closed, ivs, subject=subject)
