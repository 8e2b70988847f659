"""precond_generate_s (preconditioner, host clock): conversion plus
``make_preconditioner`` to ``block_until_ready``, mean per request.  Only
mixes that bring a new operator with each request have it."""


def read(ctx):
    spent = [r["clock"]["convert"] + r["clock"]["generate"]
             for r in ctx.requests if "generate" in r["clock"]]
    return sum(spent) / len(spent) if spent else None
