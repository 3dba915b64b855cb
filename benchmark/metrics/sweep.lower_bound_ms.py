"""Host time per plan in the sweep's aggregate-capacity lower bound,
`sweep/lower-bound` (see span_ms.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from span_ms import span_ms  # noqa: E402


def read(ctx):
    return span_ms(ctx, "plan", ("sweep/lower-bound",))
