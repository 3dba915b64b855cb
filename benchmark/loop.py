"""The closed loop that plan and simulate cells share: whole operations
back to back until the clock passes the window, a seeded reservoir of
results kept for the check, and the program's per-phase host times
summed over the window."""

from __future__ import annotations

import random
import time
from typing import Callable, Dict, List

import numpy as np


class Reservoir:
    """A uniform sample of `k` of the results seen, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = random.Random(seed)
        self.seen = 0
        self.items: List = []

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = self.rng.randrange(self.seen + 1)
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def phases_now() -> Dict[str, List[float]]:
    """{phase: [seconds, count]} of the program's GLOBAL trace."""
    from open_simulator_tpu.utils.trace import GLOBAL

    return {
        p["name"]: [p["seconds"], p["count"]] for p in GLOBAL.as_dict()["phases"]
    }


def add_phases(total: Dict[str, List[float]], more: Dict[str, List[float]]) -> None:
    for k, (s, c) in more.items():
        t = total.setdefault(k, [0.0, 0])
        t[0] += s
        t[1] += c


def _usage() -> List[float]:
    """[cpu s, minor faults, major faults, voluntary and involuntary
    context switches] of this process so far."""
    import resource

    r = resource.getrusage(resource.RUSAGE_SELF)
    return [r.ru_utime + r.ru_stime, r.ru_minflt, r.ru_majflt, r.ru_nvcsw, r.ru_nivcsw]


def run_window(seconds: float, op: Callable[[int], object], keep: Reservoir) -> dict:
    """Call `op(i)` back to back until `seconds` have passed; the window
    ends with the last whole operation. Each op starts from a reset
    GLOBAL trace, and its phases are added up here. Per op it keeps the
    wall time, the process's resource use and the phases, so that a
    slow op can be told apart (`slowest`)."""
    import jax

    from open_simulator_tpu.runtime.guard import degradations
    from open_simulator_tpu.utils.trace import GLOBAL

    phases: Dict[str, List[float]] = {}
    op_s: List[float] = []
    per_op: List[dict] = []
    degraded = 0
    t0 = time.perf_counter()
    i = 0
    while True:
        GLOBAL.reset()
        u = _usage()
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/op"):
            out = op(i)
        op_s.append(time.perf_counter() - a)
        mine = phases_now()
        per_op.append({"usage": [y - x for x, y in zip(u, _usage())],
                       "phases": {k: v[0] for k, v in mine.items()}})
        add_phases(phases, mine)
        degraded += len(degradations())
        keep.offer((i, out))
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    return {
        "elapsed_s": time.perf_counter() - t0,
        "ops": i,
        "op_s": op_s,
        "per_op": per_op,
        "phases": phases,
        "degradations": degraded,
    }


def slowest(rec: dict) -> str:
    """The slowest op of the window beside the median one: wall, cpu,
    faults, context switches and the phases that grew most."""
    if not rec.get("op_s"):
        return ""
    order = np.argsort(rec["op_s"])
    lo, hi = int(order[len(order) // 2]), int(order[-1])

    def line(j):
        cpu, mnf, mjf, vcs, ivcs = rec["per_op"][j]["usage"]
        return (f"op {j}: {rec['op_s'][j]:.3f}s wall, {cpu:.3f}s cpu, faults {mnf:.0f}/{mjf:.0f}, "
                f"ctx switches {vcs:.0f}/{ivcs:.0f}")

    ph_lo, ph_hi = rec["per_op"][lo]["phases"], rec["per_op"][hi]["phases"]
    grew = sorted(((ph_hi.get(k, 0.0) - ph_lo.get(k, 0.0), k) for k in set(ph_lo) | set(ph_hi)),
                  reverse=True)[:3]
    return (f"slowest {line(hi)}; median {line(lo)}; phases grown "
            + ", ".join(f"{k} +{d:.3f}s" for d, k in grew))


def program_counts(node_status, names: List[str], class_index: Dict[str, int],
                   label: str, extra: int = 0) -> np.ndarray:
    """[N, C] pods of each class on each node as the program reported
    them. Nodes are matched by name; `extra` further nodes (the
    planner's new nodes, named by the program) follow in report order."""
    where = {n: i for i, n in enumerate(names)}
    out = np.zeros((len(names) + extra, len(class_index)), np.int64)
    k = len(names)
    for ns in node_status:
        name = (ns.node.get("metadata") or {}).get("name")
        i = where.get(name)
        if i is None:
            if k >= out.shape[0]:
                raise ValueError(f"unexpected node {name!r} in the program's answer")
            i = k
            k += 1
        for pod in ns.pods:
            cls = ((pod.get("metadata") or {}).get("labels") or {}).get(label)
            out[i, class_index[cls]] += 1
    return out
