"""iterations (solver, program counter): ``SolveResult.iterations``, mean
over the window's requests."""


def read(ctx):
    return sum(r["iterations"] for r in ctx.requests) / len(ctx.requests)
