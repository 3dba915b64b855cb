"""Process-wide flight recorder (docs/OBSERVABILITY.md).

Three cooperating pieces, all off by default and costing nothing on the
hot path until a CLI flag turns them on:

- ``obs.spans``: thread-safe hierarchical wall-clock spans (context
  manager + decorator, contextvar parent tracking so dispatcher threads
  and nested phases nest correctly) with Chrome trace-event JSON and
  streaming JSONL exporters — ``--trace-out``.
- ``obs.explain``: per-pod placement explanations — per-node filter
  verdicts and score vectors captured at commit/failure time on both
  the serial oracle and the scan-replay paths — ``--explain [POD]``.
- ``obs.profile``: JAX dispatch / jit-cache-miss (recompile) / device
  transfer-bytes accounting through the ``utils.trace.Counters``
  registry, plus the ``--profile-dir`` JAX profiler capture: one
  capture of the whole command, in which every ``utils.trace.phase``
  is an annotation.

The compiled-cost & memory observatory (r10) layers four more pieces
on the same registry, all always-on:

- ``obs.costs``: per-site AOT compile cache — ``jit(...).lower()
  .compile()`` per shape-signature with ``cost_analysis()`` /
  ``memory_analysis()`` extracted and the artifact reused for the
  dispatch;
- ``obs.ledger``: device-memory ledger — ``memory_stats()`` /
  live-buffer polling, per-top-level-span HBM watermarks, and
  ``predict_fit`` feeding the guard's predictive degradation ladder;
- ``obs.histo``: fixed-64-bucket streaming latency histograms per jit
  site and serve request phase (p50/p95/p99, Prometheus exposition);
- ``obs.doctor``: the bench-record regression differ behind
  ``simon doctor`` and ``bench.py --against``.

``obs.profile`` (and the cost/ledger/histo trio it wires together) is
deliberately NOT imported here: it imports ``utils.trace`` for the
counter registry, and ``utils.trace`` imports ``obs.spans`` for the
phase shim — importing profile at package level would close that
cycle while ``utils.trace`` is still initializing.
"""

from . import explain, spans
from .explain import EXPLAIN
from .spans import RECORDER, span, traced

__all__ = [
    "EXPLAIN",
    "RECORDER",
    "explain",
    "span",
    "spans",
    "traced",
]
