"""Host time per simulate in `engine/failure-reasons`: failure messages
of pods no node took, once per class and run of failures (see
span_ms.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from span_ms import span_ms  # noqa: E402


def read(ctx):
    return span_ms(ctx, "preempt", ("engine/failure-reasons",))
