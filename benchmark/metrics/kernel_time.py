"""Device time of the scan's kernels, by XLA module name in the trace:
the fused Pallas kernel runs as module `jit_call` (the program gives it
no name of its own; PERF.md asks for one), the XLA scan as
`jit__run_scan_compiled_impl` and the other `*scan*` jits."""

import re

SCAN = re.compile(r"^jit_call$|scan")


def scan_ms_per_op(ctx):
    red = ctx.get("trace")
    if not red:
        return None
    s = sum(v for k, v in red["module_seconds"].items() if SCAN.search(k))
    if s <= 0:
        return None
    return 1000.0 * s / ctx["ops"]
