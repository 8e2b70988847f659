"""The comparison that decides ``correct``: each answer against float64.

An answer is the solution ``x`` a request got back.  The reference applies
the request's own operator to it in float64 on the host and reads two
numbers: the relative residual in the 2-norm, which is what the
configuration's tolerance bounds, and in the max-norm, which one wrong
entry of ``x`` cannot hide in.  A run's number is the largest over the
answers it checked; each has a limit of its own, kept per cell in
``limits/<cell>.json`` with the readings it was set from.
"""

from __future__ import annotations

import json
import os

import numpy as np

#: the numbers compared, in the order they are printed
NUMBERS = ("resid", "resid_inf")


def csr_matvec_f64(indptr, indices, values, x) -> np.ndarray:
    """``A @ x`` in float64 on the host from CSR arrays."""
    m = indptr.shape[0] - 1
    rows = np.repeat(np.arange(m), np.diff(indptr))
    prod = values.astype(np.float64) * np.asarray(x, np.float64)[indices]
    return np.bincount(rows, weights=prod, minlength=m)


def readings(indptr, indices, values, b, x) -> dict:
    """The compared numbers of one answer ``x`` to ``A x = b``."""
    b64 = np.asarray(b, np.float64)
    r = b64 - csr_matvec_f64(indptr, indices, values, x)
    return {
        "resid": float(np.linalg.norm(r) / np.linalg.norm(b64)),
        "resid_inf": float(np.abs(r).max() / np.abs(b64).max()),
    }


def load_limits(path: str) -> dict:
    """``{number: limit}`` from a cell's limits file."""
    with open(path) as f:
        spec = json.load(f)
    missing = [k for k in NUMBERS if k not in spec]
    if missing:
        raise KeyError(f"{os.path.basename(path)} has no limit for {missing}")
    return {k: float(spec[k]["limit"]) for k in NUMBERS}


def judge(per_answer: list, limits: dict) -> tuple:
    """``(correct, failed, worst)``: ``worst`` maps each number to the largest
    reading with its limit.  A NaN reading, or no answer at all, fails."""
    failed = sum(
        1 for r in per_answer if not all(r[k] <= limits[k] for k in NUMBERS)
    )
    worst = {k: {"value": _worst([r[k] for r in per_answer]), "limit": limits[k]}
             for k in NUMBERS}
    return bool(per_answer) and failed == 0, failed, worst


def _worst(values: list) -> float:
    """The largest reading; NaN where any is NaN or there is none."""
    if not values or any(v != v for v in values):
        return float("nan")
    return max(values)
