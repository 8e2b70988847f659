"""mg_coarse_share (preconditioner, trace): the share of the multigrid
cycle's device time spent away from the fine level: device self time under
the program's scope ``Multigrid.apply`` but not under ``Multigrid.level0``
(every restriction and prolongation, the coarser levels and the coarse
solve), over the device self time under ``Multigrid.apply``, in %.  ``None``
where no op ran under ``Multigrid.apply``."""

from chipbench import scopes

scopes.enable_for_traced_run()


def read(ctx):
    if ctx.summary is None or ctx.lib.distributed:
        return None
    cycle = scopes.seconds_under(ctx, "Multigrid.apply")
    if cycle is None:
        return None
    trace = scopes.load(ctx)
    fine = scopes.scope_seconds(trace, ("Multigrid.level0",))
    return 100.0 * (cycle - fine) / cycle
