"""Kernel admissibility pass — launch configs proven before they compile.

A fused launch can be *numerically* sound (the bound pass) and still be an
impossible kernel: an operand tile that blows the VMEM budget, a block with
a zero extent, a channel whose modulus does not fit the 15-bit SMEM Horner
tables, a committed tune-table row that `blocks_for` would admit but the
device would reject.  This pass validates the launch geometry statically,
reusing the *same* constants the runtime uses (`tune.vmem_footprint`,
`tune.VMEM_BUDGET_BYTES`, `multiword.MAX_HORNER_MODULUS`) so the check can
never drift from the kernel (DESIGN.md §16).

The fused kernel pads operands to block multiples (``(-M) % bm``), so block
divisibility is never a hard error — gross padding waste is reported as a
warning instead.
"""
from __future__ import annotations

from typing import Mapping, Optional, Sequence, Tuple

from repro.core import multiword as mw
from repro.kernels import tune

from .findings import Report

__all__ = ["check_launch", "check_basis_tables", "check_tune_table",
           "check_config_launches"]

Blocks = Tuple[int, int, int]


def check_launch(M: int, K: int, N: int, C: int, blocks: Blocks, *,
                 x_channels: bool = False, emit: bool = False,
                 itemsize: int = 1, subject: str = "launch") -> Report:
    """Prove one (shape, tiling) pair admissible for the fused kernel."""
    rep = Report(subject=f"admissibility:{subject}")
    bm, bn, bk = (int(b) for b in blocks)
    for name, b in (("block_m", bm), ("block_n", bn), ("block_k", bk)):
        if b <= 0:
            rep.add("admissibility", f"{name}={b}",
                    "non-positive block extent — the grid would be empty")
    if min(bm, bn, bk) <= 0:
        return rep
    clipped = tune.clip_blocks((bm, bn, bk), M, K, N)
    foot = tune.vmem_footprint(clipped, C, itemsize=itemsize,
                               x_channels=x_channels, emit=emit)
    if foot > tune.VMEM_BUDGET_BYTES:
        rep.add("admissibility", f"blocks={clipped} C={C}",
                f"VMEM footprint {foot} bytes exceeds the "
                f"{tune.VMEM_BUDGET_BYTES}-byte budget "
                f"(x_channels={x_channels}, emit={emit})")
    # Padding to block multiples is legal but can dominate tiny shapes.  The
    # row pad up to the sublane tile is the floor any tiling pays, so the
    # inflation is measured against that.
    cbm, cbn, cbk = clipped
    m_tile = -(-M // tune.SUBLANE_TILE) * tune.SUBLANE_TILE
    padded = ((M + cbm - 1) // cbm * cbm) * ((N + cbn - 1) // cbn * cbn)
    if padded > 4 * m_tile * N:
        rep.add("admissibility", f"blocks={clipped} shape=M{M}xN{N}",
                f"padding inflates the output grid {padded / (M * N):.1f}x "
                f"— tile the launch smaller", severity="warning")
    return rep


def check_basis_tables(moduli: Sequence[int], *,
                       subject: str = "basis") -> Report:
    """SMEM-table admissibility of a channel basis.

    The kernel's per-channel fold constants and the MRC limb Horner walk
    both index SMEM tables built for moduli ``m <= 2^15``
    (`multiword.MAX_HORNER_MODULUS`); a wider channel silently falls back to
    host reversal, which breaks residency — so it is an error here.
    """
    rep = Report(subject=f"admissibility:{subject}")
    for m in moduli:
        m = int(m)
        if m < 2:
            rep.add("admissibility", f"channel m={m}",
                    "modulus below 2 carries no information")
        elif m > mw.MAX_HORNER_MODULUS:
            rep.add("admissibility", f"channel m={m}",
                    f"modulus exceeds the 15-bit SMEM Horner limit "
                    f"2^15={mw.MAX_HORNER_MODULUS} — reverse conversion "
                    f"cannot stay on device")
    return rep


def check_tune_table(table: Mapping[str, object], *,
                     subject: str = "tune_table") -> Report:
    """Validate every committed tune-table row: parseable key, 3 positive
    block extents, VMEM-admissible for the variant the key names."""
    rep = Report(subject=f"admissibility:{subject}")
    for key, val in table.items():
        try:
            parsed = tune.parse_shape_key(key)
        except ValueError as e:
            rep.add("admissibility", f"key {key!r}", str(e))
            continue
        if (not isinstance(val, (list, tuple)) or len(val) != 3
                or not all(isinstance(v, int) and not isinstance(v, bool)
                           for v in val)):
            rep.add("admissibility", f"key {key!r}",
                    f"entry {val!r} is not a [bm, bn, bk] triple of ints")
            continue
        sub = check_launch(parsed["M"], parsed["K"], parsed["N"],
                           parsed["C"], tuple(val),
                           x_channels=parsed["x_channels"],
                           emit=parsed["emit"], subject=key)
        rep.extend(sub)
    return rep


def check_config_launches(cfg, *, batch_sizes: Optional[Sequence[int]] = None
                          ) -> Report:
    """Admissibility of every decode launch a config's serving path makes.

    Enumerates the same shapes `Engine.__init__` warms
    (`tune.decode_shapes_for`) and proves each one's resolved tiling and
    basis tables admissible.
    """
    rep = Report(subject=f"admissibility:{getattr(cfg, 'arch', cfg)}")
    kwargs = {} if batch_sizes is None else {"batch_sizes": batch_sizes}
    for s in tune.decode_shapes_for(cfg, **kwargs):
        blocks = tune.blocks_for(
            s["M"], s["K"], s["N"], s["C"], dtype=s["dtype"],
            backend=s["backend"], x_channels=s["x_channels"], emit=s["emit"])
        rep.extend(check_launch(
            s["M"], s["K"], s["N"], s["C"], blocks,
            x_channels=s["x_channels"], emit=s["emit"],
            subject=f"{s['backend']} M{s['M']}xK{s['K']}xN{s['N']}"))
    return rep
