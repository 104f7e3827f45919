"""Operations and bytes of one kernel call, from its logical shapes.

These count the work a call asks for, not what an implementation does with
it: unpadded shapes, int32 fields at 4 bytes, every field read once and
every result written once.  A later change to a kernel's tiling or padding
leaves these numbers as they are.

Cost of one bin slot on a kind with ``M`` aspect modes: per mode two
ceiling divisions (an add and a divide each), a multiply and a running
minimum, so ``6 * M``; the empty-slot mask adds a compare and a select.  With
a kind lane each kind also costs a weight multiply, a compare and a select.
"""
from __future__ import annotations

INT32 = 4


def slot_cost_ops(kind_modes) -> int:
    """Integer operations to cost one bin slot. ``kind_modes`` is one mode
    list per RAM kind (a single list for the homogeneous kernels)."""
    if len(kind_modes) == 1:
        return 6 * len(kind_modes[0]) + 2
    return sum(6 * len(m) + 3 for m in kind_modes) + 2


def sa_step(rows: int, touched: int, kind_modes) -> tuple[int, int]:
    """``binpack_sa_step``: (rows, touched) geometry before and after a
    move -> one delta per row.  Returns ``(ops, bytes)``."""
    hetero = len(kind_modes) > 1
    fields_in = 6 if hetero else 4  # old/new width, height (and kind)
    slots = rows * touched
    ops = slots * (2 * slot_cost_ops(kind_modes) + 2)  # old, new, diff, sum
    nbytes = slots * fields_in * INT32 + rows * INT32
    return ops, nbytes


def fitness(population: int, bins: int, kind_modes) -> tuple[int, int]:
    """``binpack_fitness``: (population, bins) geometry -> a cost per bin."""
    hetero = len(kind_modes) > 1
    fields_in = 3 if hetero else 2  # width, height (and kind)
    slots = population * bins
    ops = slots * slot_cost_ops(kind_modes)
    nbytes = slots * (fields_in + 1) * INT32
    return ops, nbytes


def portfolio_step(population: int, bins: int, rows: int, touched: int,
                   kind_modes) -> tuple[int, int]:
    """``binpack_portfolio_step``: one fitness and one SA step call fused."""
    f_ops, f_bytes = fitness(population, bins, kind_modes)
    s_ops, s_bytes = sa_step(rows, touched, kind_modes)
    return f_ops + s_ops, f_bytes + s_bytes
