"""repro.serve — persistent solve service (the library meets traffic).

The ROADMAP's production-scale story for the solver stack: a long-running
service that aggregates many small incoming systems into batched masked-Krylov
launches with **continuous batching** (new systems are admitted into mask
slots as converged systems retire — :mod:`repro.serve.engine`), backed by a
**pattern-keyed setup cache** exploiting Ginkgo's generate/apply separation
(:mod:`repro.serve.cache`): expensive generation — block discovery, slot
tables, block-Jacobi inversion, jit-compiled solver closures — is keyed by
the sparsity-pattern hash, so repeat-pattern traffic pays only numeric-values
cost and repeat-values traffic pays neither.

:mod:`repro.serve.service` wraps the engine in a background thread behind an
async request queue; :mod:`repro.serve.traffic` generates synthetic Poisson
traffic over a pattern gallery for the CI smoke gate.
"""

from repro.serve.cache import (
    PatternSetup,
    SetupCache,
    pattern_key,
    values_fingerprint,
)
from repro.serve.engine import ContinuousBatchEngine, PatternLane, ServeConfig
from repro.serve.request import SolveRequest, SolveResponse
from repro.serve.service import SolveService
from repro.serve.traffic import TrafficConfig, generate_traffic, pattern_gallery

__all__ = [
    "ContinuousBatchEngine",
    "PatternLane",
    "PatternSetup",
    "ServeConfig",
    "SetupCache",
    "SolveRequest",
    "SolveResponse",
    "SolveService",
    "TrafficConfig",
    "generate_traffic",
    "pattern_gallery",
    "pattern_key",
    "values_fingerprint",
]
