"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, device time by operation name, and
the longest idle gaps labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone. The traced window is the
host annotation ``bench/window`` that the harness opens around the
measured loop; every device interval is clipped to it.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench/window"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.split(":")[1]


def _device_line(plane):
    """The line that holds one event per operation that ran: "XLA Ops"
    where the backend writes one, else the busiest line."""
    lines = list(plane.lines)
    for line in lines:
        if line.name == "XLA Ops":
            return line
    return max(lines, key=lambda l: sum(1 for _ in l.events), default=None)


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def label_gaps(idle: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[float, float, str]]) -> List[Tuple[float, float, str]]:
    """Cut every idle gap where a host span opens or closes, label each
    piece by the innermost span open over it ("host (unlabelled)" where
    none is), and merge neighbouring pieces of one label."""
    out: List[Tuple[float, float, str]] = []
    spans = sorted(spans)
    for a, b in idle:
        inside = [s for s in spans if s[0] < b and s[1] > a]
        cuts = sorted({a, b, *(t for s in inside for t in s[:2] if a < t < b)})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            open_ = [s for s in inside if s[0] <= mid <= s[1]]
            k = min(open_, key=lambda s: s[1] - s[0])[2] if open_ else "host (unlabelled)"
            if out and out[-1][2] == k and out[-1][1] == x:
                out[-1] = (out[-1][0], y, k)
            else:
                out.append((x, y, k))
    return out


def reduce(xplane_path: str, labels: Optional[Iterable[str]] = None, top: int = 10) -> dict:
    """Busy seconds (mean over device planes), window seconds, device
    seconds by XLA module, and the breakdown the result line carries.

    `labels` names the host annotations that may label idle time (the
    harness's spans and the program's phases); see label_gaps."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    labels = set(labels or ())
    window = None
    host_spans: List[Tuple[float, float, str]] = []
    per_device: List[List[Tuple[float, float, str]]] = []
    modules: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            line = _device_line(plane)
            if line is None:
                continue
            per_device.append(
                [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
            )
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    modules.extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name) for e in ln.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in labels:
                        host_spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if window is None:
        raise ValueError(f"{xplane_path}: no {WINDOW} annotation")
    lo, hi = window
    op_ns: Dict[str, float] = {}
    busy_ns, all_busy = [], []
    for events in per_device:
        clipped = [(max(a, lo), min(b, hi), n) for a, b, n in events if b > lo and a < hi]
        for a, b, n in clipped:
            # "%fusion.3 = f32[..] fusion(..), kind=.., calls=.." -> "fusion.3"
            n = n.split(" = ", 1)[0].lstrip("%")
            op_ns[n] = op_ns.get(n, 0.0) + (b - a)
        iv = [(a, b) for a, b, _ in clipped]
        busy_ns.append(union_length(iv))
        all_busy.extend(iv)
    n_dev = max(len(per_device), 1)
    mod_ns: Dict[str, float] = {}
    for a, b, n in modules:
        if b > lo and a < hi:
            name = n.split("(", 1)[0]  # jit_<fn>(<fingerprint>) -> jit_<fn>
            mod_ns[name] = mod_ns.get(name, 0.0) + min(b, hi) - max(a, lo)
    idle = gaps(all_busy, lo, hi)
    pieces = label_gaps(idle, host_spans)
    pieces.sort(key=lambda p: p[0] - p[1])
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = [[k, (b - a) / 1e9] for a, b, k in pieces[:top]]
    return {
        "devices": len(per_device),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "module_seconds": {k: v / n_dev / 1e9 for k, v in mod_ns.items()},
        "breakdown": {
            "device_ops": [[k, v / n_dev / 1e9] for k, v in top_ops],
            "idle_gaps": top_gaps,
        },
    }
