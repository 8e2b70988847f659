"""precond_apply_loop_ms (preconditioner, trace): the preconditioner's apply
as it runs inside the CG loop, found by the program's scope
``<ClassName>.apply``: its gather and scatter, and the apply kernel between
them.  Device self time under the scope per apply, in ms; one apply per
iteration and one per solve.  ``None`` where no op ran under it."""

from chipbench import scopes

#: the scope of each preconditioner kind's apply
APPLY_SCOPE = {"block_jacobi": "BlockJacobi.apply"}

scopes.enable_for_traced_run()


def read(ctx):
    scope = APPLY_SCOPE.get(ctx.config["precond"]["kind"])
    if ctx.summary is None or ctx.lib.distributed or scope is None:
        return None
    seconds = scopes.seconds_under(ctx, scope)
    calls = scopes.loop_calls(ctx)
    if seconds is None or calls == 0:
        return None
    return 1e3 * seconds / calls
