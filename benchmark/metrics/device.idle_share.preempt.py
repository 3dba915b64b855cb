"""Device idle share of the traced window in a preemption cell (see
idle_share.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from idle_share import idle_pct  # noqa: E402


def read(ctx):
    return idle_pct(ctx, "preempt")
