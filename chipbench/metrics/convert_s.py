"""convert_s (format, program span): seconds a request spends in the program's
span ``sparse.ell_from_csr_host`` (host CSR to ELL conversion), read from the
profiler trace's host plane.  Only mixes that bring a new operator with each
request have it; ``None`` (and one line on standard error) where the window
holds no such span."""

import sys

from chipbench import scopes

SPAN = "sparse.ell_from_csr_host"

scopes.enable_for_traced_run()


def read(ctx):
    trace = scopes.load(ctx)
    if trace is None:
        return None
    seconds = scopes.span_seconds(trace, SPAN)
    if seconds <= 0.0:
        print(f"chipbench: no program span {SPAN} in the window", file=sys.stderr)
        return None
    return seconds / len(ctx.requests)
