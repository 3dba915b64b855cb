"""One-shot ``scheduler.core.simulate`` calls on the TPU engine, back to
back, over a cluster whose nodes are full of low-priority pods, placing
high-priority pods that fit only by preempting (traffic kind "preempt").

Every simulate places the same pods under a fresh Deployment name, with
the program's memos cleared, as drivers/simulate.py does. scenario.py
reads no priority, so the Deployment's pod template gets `spec.priority`
here, from the configuration's template. Each simulate carries a
deadline (the traffic's `op_deadline_s`), so a program that falls back
to the serial preemption cycle fails within it. The program checks
its budget only between pods, so an alarm here holds every simulate to
the same deadline from outside: `op_deadline_s` in the window, and
`warm_deadline_s` for the warm operation, whose compiles it must
cover. Set-up fails if a preemption escaped to that cycle. Checked:
for a seeded sample of the window's simulates, the placements per node
and class, the unscheduled count, and the node and victim count of
every preemption in order, against reference_preempt.py.
"""

from __future__ import annotations

import contextlib
import signal
import sys

import numpy as np

from benchmark import loop, preempt_work, reference_preempt
from benchmark.scenario import CLASS_LABEL, class_of, scaled

KEEP = 4
# program counters read over the window (absent where the program has none)
COUNTED = ("preempt_serial_escapes_total", "preempt_device_total", "preempt_victims_total")


def _counts() -> dict:
    from open_simulator_tpu.utils.trace import COUNTERS

    now = COUNTERS.snapshot()["counts"]
    return {k: now[k] for k in COUNTED if k in now}


@contextlib.contextmanager
def deadline(seconds: float):
    """Raise TimeoutError in the calling thread once `seconds` have
    passed inside the block."""

    def expire(signum, frame):
        raise TimeoutError(f"benchmark: a simulate ran past its {seconds:g} s deadline")

    old = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


class Driver:

    def __init__(self, scn, seed: int):
        self.scn = scn
        self.seed = seed
        self.entries = [
            (class_of(scn.classes, e["template"], e.get("namespace"), scn.config),
             scaled(int(e["replicas"]), scn.scale))
            for e in scn.traffic["workload"]
        ]
        self.pods_per_op = sum(r for _, r in self.entries)
        tmpl = scn.config["templates"]
        self.prios = [int(tmpl[pc.template].get("priority", 0)) for pc in scn.classes]
        self.deadline_s = float(scn.traffic["op_deadline_s"])
        self.warm_deadline_s = float(scn.traffic["warm_deadline_s"])
        self.keep = loop.Reservoir(KEEP, seed)
        self.salt = f"{seed % 1000003:06d}"

    def setup(self) -> None:
        self.cluster = self.scn.cluster()
        before = _counts().get("preempt_serial_escapes_total", 0)
        try:
            self.op(-1, self.warm_deadline_s)  # compiles, or loads from the cache, every shape
        except TimeoutError as e:
            raise SystemExit(str(e)) from None
        escaped = _counts().get("preempt_serial_escapes_total", 0) - before
        if escaped:
            raise SystemExit(f"benchmark: {escaped} preemption(s) took the serial cycle")

    def op(self, i: int, deadline_s: float = None):
        from open_simulator_tpu.models.decode import ResourceTypes
        from open_simulator_tpu.runtime.budget import Budget
        from open_simulator_tpu.scheduler.core import AppResource, simulate
        from open_simulator_tpu.utils.memo import clear_all_memos

        scn = self.scn
        res = ResourceTypes()
        res.deployments = []
        for c, r in self.entries:
            dep = scn.deployment(c, r, f"{scn.classes[c].template}-{self.salt}-{i + 1}")
            if self.prios[c]:
                dep["spec"]["template"]["spec"]["priority"] = self.prios[c]
            res.deployments.append(dep)
        seconds = deadline_s or self.deadline_s
        with deadline(seconds):
            out = simulate(self.cluster, [AppResource(f"sim-{i + 1}", res)], engine="tpu",
                           budget=Budget(deadline_s=seconds))
        clear_all_memos()
        return out

    def window(self, seconds: float) -> dict:
        c0 = _counts()
        self.rec = loop.run_window(seconds, self.op, self.keep)
        self.rec["counters"] = {k: v - c0.get(k, 0) for k, v in _counts().items()}
        return self.rec

    def metrics(self) -> dict:
        return {"sim_pods_per_s": self.pods_per_op * self.rec["ops"] / self.rec["elapsed_s"]}

    def context(self) -> dict:
        done = self.rec["counters"].get("preempt_device_total")
        if done is not None:
            nodes = len(self.scn.tables.names)
            slots = int(self.scn.config["nodes"]["pods"])
            per_op = done / self.rec["ops"]
            work = preempt_work.dry_run_slot_visits(per_op, nodes, slots)
            print(f"preempt dry run per simulate: {per_op:g} preemptions x {nodes} nodes x "
                  f"{slots} slots = {work:g} slot visits", file=sys.stderr, flush=True)
        return {"kind": "preempt", **self.rec}

    def attempted_failed(self):
        return self.rec["ops"], 0  # a simulate that raises ends the run

    def release(self) -> None:
        scn = self.scn
        where = {name: i for i, name in enumerate(scn.tables.names)}
        self.kept = []
        for _, res in self.keep.items:
            events = []
            for ev in res.preemptions:
                key = (ev.preemptor, where.get(ev.node_name, -1))
                if events and events[-1][0] == key:
                    events[-1][1] += 1
                else:
                    events.append([key, 1])
            self.kept.append((
                len(res.unscheduled_pods),
                loop.program_counts(res.node_status, scn.tables.names,
                                    scn.class_index, CLASS_LABEL),
                [(key[1], count) for key, count in events],
            ))
        self.keep.items = []
        self.cluster = None

    def check(self, precision: str = "high") -> dict:
        scn = self.scn
        # PrioritySort: priority descending, queue order kept on ties
        seq = sorted(scn.ordered(self.entries), key=lambda c: -self.prios[c])
        ref = reference_preempt.schedule(scn.tables, scn.classes, self.prios, scn.bound,
                                         seq, scn.taint_keys, precision)
        mismatch = max((int(np.abs(got - ref.counts).sum()) for _, got, _ in self.kept),
                       default=0)
        gap = max((abs(u - ref.unscheduled) for u, _, _ in self.kept), default=0)
        pre = max(
            (sum(a != b for a, b in zip(ev, ref.events)) + abs(len(ev) - len(ref.events))
             for _, _, ev in self.kept),
            default=0,
        )
        return {"placement_mismatch": (mismatch, 0), "unscheduled_gap": (gap, 0),
                "preemption_mismatch": (pre, 0)}
