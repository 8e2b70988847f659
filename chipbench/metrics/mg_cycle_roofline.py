"""mg_cycle_roofline (preconditioner, trace): one V(1,1)-cycle of the
program's multigrid as it runs inside the CG loop, found by the program's
scope ``Multigrid.apply``.  Least bytes of a cycle (:func:`cycle_min_bytes`,
from the sizes the program counts in its gauges ``amg_level_rows``,
``amg_level_nnz`` and ``amg_transfer_nnz``) over the HBM peak, divided by the
device self time under the scope per apply, in %; one apply per iteration
and one per solve.  Bandwidth bounds it.  ``None`` where the program counts
no transfers or no op ran under the scope."""

import numpy as np

from chipbench import scopes

SCOPE = "Multigrid.apply"

scopes.enable_for_traced_run()


def hierarchy_sizes(samples) -> list:
    """``[(rows, nnz, transfer_nnz)]`` of each level above the coarsest, then
    ``(rows, nnz, None)`` of the coarsest, from the program's gauges
    (``repro.observability.metrics.samples()``).  The levels above the
    coarsest are those that count transfers, from level 0 on; ``[]`` where
    there is none, or a level's rows or entries are not there."""
    gauges = {(s["name"], s["labels"].get("level")): int(s["value"]) for s in samples
              if s["name"] in ("amg_level_rows", "amg_level_nnz", "amg_transfer_nnz")}
    depth = 0
    while ("amg_transfer_nnz", str(depth)) in gauges:
        depth += 1
    sizes = []
    for k in range(depth + 1):
        if any((key, str(k)) not in gauges for key in ("amg_level_rows", "amg_level_nnz")):
            return []
        transfer = gauges["amg_transfer_nnz", str(k)] if k < depth else None
        sizes.append((gauges["amg_level_rows", str(k)], gauges["amg_level_nnz", str(k)],
                      transfer))
    return sizes if depth else []


def cycle_min_bytes(sizes, itemsize: int) -> int:
    """Least bytes of one V(1,1)-cycle from a zero guess.  On each level
    above the coarsest: the operator's values twice (the residual after the
    pre-sweep, which from a zero guess needs no SpMV, and the post-sweep),
    the stored values of ``P`` and ``R`` once each, the inverse diagonal once
    a sweep; on the coarsest level its dense inverse once.  Index bytes,
    padding and vectors are not counted."""
    values = sum(2 * nnz + transfer + 2 * rows for rows, nnz, transfer in sizes[:-1])
    return (values + sizes[-1][0] ** 2) * itemsize


def read(ctx):
    if ctx.summary is None or ctx.lib.distributed:
        return None
    from repro.observability import metrics

    sizes = hierarchy_sizes(metrics.samples())
    if not sizes:
        return None
    seconds = scopes.seconds_under(ctx, SCOPE)
    calls = scopes.loop_calls(ctx)
    if seconds is None or calls == 0:
        return None
    least = cycle_min_bytes(sizes, np.dtype(ctx.config["dtype"]).itemsize)
    return 100.0 * least / ctx.peak("hbm_bytes_per_s") / (seconds / calls)
