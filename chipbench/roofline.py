"""Peaks of the chip and the least bytes a kernel or an iteration must move.

Both kernels here are bound by memory bandwidth (a few operations per byte
against the chip's hundreds), so a roofline share is the least bytes over
the HBM peak, divided by the measured device time.  The counts come from
shapes alone: index bytes and padding are not counted, since they change
with the storage format, and a share stays under 100% whatever format a
later version of the program picks.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str, key: str) -> float:
    """A published peak of ``device_kind``; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return float(table[device_kind][key])


def spmv_min_bytes(n: int, nnz: int, itemsize: int = 4) -> int:
    """``y = A x``: every stored value read once, ``x`` read and ``y`` written."""
    return nnz * itemsize + 2 * n * itemsize


def precond_operand_bytes(n: int, precond: dict, itemsize: int = 4) -> int:
    """What a preconditioner's apply must read besides its input vector, as
    its module (``preconds/<kind>.py``) counts it."""
    from chipbench.bench import part

    opts = {k: v for k, v in precond.items() if k != "kind"}
    return int(part("preconds", precond["kind"]).operand_bytes(n, opts, itemsize))


def cg_iter_min_bytes(n: int, nnz: int, precond: dict, itemsize: int = 4) -> int:
    """One preconditioned CG iteration with every pass fused as far as its
    two reductions allow: the stored values and the preconditioner's operand
    read once, and the updated vectors ``x``, ``r``, ``p`` each read and
    written once."""
    return nnz * itemsize + precond_operand_bytes(n, precond, itemsize) + 6 * n * itemsize
