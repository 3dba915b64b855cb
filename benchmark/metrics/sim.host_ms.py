"""Host time per simulate: the harness's span around each simulate,
less the scan kernel's device time in the window (kernel_time.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from kernel_time import scan_ms_per_op  # noqa: E402


def read(ctx):
    if ctx.get("kind") != "simulate" or not ctx.get("trace"):
        return None
    kernel = scan_ms_per_op(ctx) or 0.0
    return 1000.0 * sum(ctx["op_s"]) / ctx["ops"] - kernel
