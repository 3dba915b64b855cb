"""Device time of the scan kernels per simulate in a preemption cell
(see kernel_time.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from kernel_time import scan_ms_per_op  # noqa: E402


def read(ctx):
    return scan_ms_per_op(ctx) if ctx.get("kind") == "preempt" else None
