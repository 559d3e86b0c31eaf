"""Public jit'd entry points for the Pallas kernels.

Backend dispatch: on TPU the kernels compile natively (interpret=False);
everywhere else (CPU hosts, unit tests) they run in interpret mode, which
executes the kernel body in Python for bit-exact validation against
`ref.py`.  The device-aware selection lives in
`core/channel_plan.resolve_interpret`; every kernel resolves
``interpret=None`` itself, so these wrappers only coerce static
arguments.  Callers can force either mode.
"""
from __future__ import annotations

from repro.core.conversion_plan import ConversionPlan

from . import ref
from .flash_attention import flash_attention as _flash_attention
from .fold import fold as _fold
from .rns_convert import rns_forward as _rns_forward
from .rns_convert import rns_reverse as _rns_reverse
from .rns_fused import rns_fused_matmul  # noqa: F401  (resolves its own args)
from .rns_matmul import rns_matmul as _rns_matmul
from .rns_modmul import rns_modmul as _rns_modmul

__all__ = ["rns_matmul", "rns_fused_matmul", "rns_modmul", "rns_forward",
           "rns_reverse", "fold", "flash_attention", "ref"]


def rns_matmul(a_res, b_res, moduli, *, interpret=None, **kw):
    return _rns_matmul(a_res, b_res, tuple(int(m) for m in moduli),
                       interpret=interpret, **kw)


def rns_forward(x, moduli, *, interpret=None, **kw):
    return _rns_forward(x, tuple(int(m) for m in moduli),
                        interpret=interpret, **kw)


def rns_reverse(residues, moduli, *, interpret=None, **kw):
    return _rns_reverse(residues, ConversionPlan.build(moduli),
                        interpret=interpret, **kw)


def rns_modmul(a_res, b_res, moduli, *, interpret=None, **kw):
    return _rns_modmul(a_res, b_res, tuple(int(m) for m in moduli),
                       interpret=interpret, **kw)


def fold(x, moduli, bound, *, interpret=None, **kw):
    return _fold(x, tuple(int(m) for m in moduli), int(bound),
                 interpret=interpret, **kw)


def flash_attention(q, k, v, *, interpret=None, **kw):
    return _flash_attention(q, k, v, interpret=interpret, **kw)
