"""The one traffic generator: a mix file's parameters and a seed -> requests.

A mix (``traffic/<name>.json``) says:

- ``operator``: ``"fixed"`` (one operator, converted and generated in
  set-up; a request brings only a right-hand side) or ``"per_request"``
  (each request brings new values on the same pattern, which the library
  converts and generates from before it solves);
- ``diag_shift_dt``: the cycle of time steps ``dt``; pool entry ``i`` is
  ``A_i = L + I / dt[i mod len]`` (empty: ``A = L``);
- ``pool``: how many requests set-up makes; request ``j`` is pool entry
  ``j mod pool``;
- ``requests_per_round``: the window closes only after a whole round, so
  every run's window holds the same mix.

Each request's ``b = A x*`` is computed in float64 and rounded to the
configuration's dtype, as are the operator's values.  ``x*`` is standard
normal.  In the timed runs pool entry ``i`` draws it from the fixed stream
``i``, and the seed picks one of the system's symmetry images of it
(reflections, a swap of axes, the sign).  So every seed gets the same
operators in the same order and, image for image, the same right-hand
sides: the same work, on different data.  With ``x*`` drawn from the seed
itself, CG's iteration count at 128^3 moved by 15% from seed to seed (106
against 122), because the few slowest modes' random weights decide when the
residual crosses the tolerance.  The readings that set the limits of
``correct`` (``readings.py``) draw ``x*`` from the seed itself
(``xstar="seed"``), so the limits rest on as many right-hand sides as seeds.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.check import csr_matvec_f64

#: the random stream of the pool's base solutions, the same for every seed
BASE_STREAM = 12

MIX_KEYS = ("operator", "diag_shift_dt", "pool", "requests_per_round")


@dataclasses.dataclass(frozen=True)
class Request:
    values: np.ndarray  # CSR values of this request's operator, in the stated dtype
    b: np.ndarray  # right-hand side, in the stated dtype
    shift: float = 0.0  # the operator is L + shift I


def validate_mix(mix: dict, name: str = "mix") -> dict:
    missing = [k for k in MIX_KEYS if k not in mix]
    if missing:
        raise KeyError(f"traffic {name} lacks {missing}")
    if mix["operator"] not in ("fixed", "per_request"):
        raise ValueError(f"traffic {name}: operator must be fixed or per_request")
    if mix["operator"] == "fixed" and len(mix["diag_shift_dt"]) > 1:
        raise ValueError(f"traffic {name}: a fixed operator has one shift at most")
    if int(mix["pool"]) < 1 or int(mix["requests_per_round"]) < 1:
        raise ValueError(f"traffic {name}: pool and requests_per_round must be >= 1")
    return mix


def shifts(mix: dict) -> list:
    """The diagonal shift of each pool entry."""
    dts = list(mix["diag_shift_dt"])
    return [1.0 / dts[i % len(dts)] if dts else 0.0 for i in range(int(mix["pool"]))]


def make_pool(system, mix: dict, seed: int, dtype="float32", xstar: str = "image") -> list:
    """The requests of one run, drawn from ``seed``: ``x*`` is an image of a
    fixed draw (``"image"``, the timed runs) or drawn from the seed
    (``"seed"``, the readings behind the limits)."""
    if xstar not in ("image", "seed"):
        raise ValueError(f"xstar must be image or seed, not {xstar!r}")
    seed = int(seed) % (1 << 64)
    rng = np.random.default_rng(seed)
    images = rng.integers(0, system.images, size=int(mix["pool"]))
    pool = []
    for i, (shift, image) in enumerate(zip(shifts(mix), images)):
        values = system.shifted_values(shift).astype(dtype, copy=False)
        if xstar == "seed":
            xs = np.random.default_rng([seed, i]).standard_normal(system.n)
        else:
            base = np.random.default_rng([BASE_STREAM, i]).standard_normal(system.n)
            xs = system.image(base, int(image))
        b = csr_matvec_f64(system.indptr, system.indices, values, xs)
        pool.append(Request(values, b.astype(dtype), shift))
    return pool
