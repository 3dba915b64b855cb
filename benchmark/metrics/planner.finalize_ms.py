"""Host time per plan in `probe_plan`'s closing `apply/clear-memos` +
`apply/gc`, the cyclic collection of the plan's garbage (see span_ms.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from span_ms import span_ms  # noqa: E402


def read(ctx):
    return span_ms(ctx, "plan", ("apply/clear-memos", "apply/gc"))
