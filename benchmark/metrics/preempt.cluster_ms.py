"""Host time per simulate in `sim/run-cluster`: the running cluster
rebuilt before the preemptors arrive (see span_ms.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from span_ms import span_ms  # noqa: E402


def read(ctx):
    return span_ms(ctx, "preempt", ("sim/run-cluster",))
