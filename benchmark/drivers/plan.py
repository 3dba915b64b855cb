"""Capacity plans of the whole cluster, back to back, through
``apply.applier.probe_plan`` (traffic kind "plan").

Checked: every kept plan's answer (the new-node count) and the
placements behind it, per node and class, against the reference at
that count; at one node fewer the pods must ask more cpu, memory or
pod slots than the nodes hold, or else the reference must leave a pod
unplaced there, so the count is the least that works.
"""

from __future__ import annotations

import numpy as np

from benchmark import loop, reference
from benchmark.scenario import CLASS_LABEL, class_of, scaled

KEEP = 2  # plans kept for the check (each holds every pod of the plan)


class Driver:

    def __init__(self, scn, seed: int):
        self.scn = scn
        self.seed = seed
        self.entries = [
            (class_of(scn.classes, e["template"], e.get("namespace"), scn.config),
             scaled(int(e["replicas"]), scn.scale))
            for e in scn.traffic["workload"]
        ]
        self.keep = loop.Reservoir(KEEP, seed)
        self.failed = 0
        self.answers = set()

    def setup(self) -> None:
        from open_simulator_tpu.models.decode import ResourceTypes
        from open_simulator_tpu.scheduler.core import AppResource

        scn = self.scn
        res = ResourceTypes()
        res.deployments = [
            scn.deployment(c, r, scn.classes[c].name) for c, r in self.entries
        ]
        self.cluster = scn.cluster()
        self.apps = [AppResource("plan", res)]
        self.new_node = scn.new_node()
        self.max_count = scaled(int(scn.config["new_node"]["max_count"]), scn.scale)
        self.op(-1)  # compiles, or loads from the cache, every shape

    def op(self, i: int):
        from open_simulator_tpu.apply.applier import probe_plan
        from open_simulator_tpu.models.workloads import reset_name_counter

        reset_name_counter()
        res = probe_plan(self.cluster, self.apps, self.new_node, max_count=self.max_count)
        if i >= 0:
            self.failed += not res.success
            self.answers.add(int(res.new_node_count))
        return res

    def window(self, seconds: float) -> dict:
        self.rec = loop.run_window(seconds, self.op, self.keep)
        return self.rec

    def metrics(self) -> dict:
        return {"plan_s": self.rec["elapsed_s"] / self.rec["ops"]}

    def context(self) -> dict:
        return {"kind": "plan", **self.rec}

    def attempted_failed(self):
        return self.rec["ops"], self.failed

    def release(self) -> None:
        """Reduce the kept plans to counts and drop the program's state."""
        scn = self.scn
        self.kept = []
        for _, res in self.keep.items:
            c = int(res.new_node_count)
            got = (
                loop.program_counts(
                    res.result.node_status, scn.tables.names, scn.class_index,
                    CLASS_LABEL, extra=c,
                )
                if res.success
                else None
            )
            self.kept.append((bool(res.success), c, got))
        self.keep.items = []
        self.cluster = self.apps = None

    def check(self, precision: str = "high") -> dict:
        """Numbers compared, each with its limit."""
        scn = self.scn
        seq = scn.ordered(self.entries)
        zone_key = scn.config["nodes"].get("zone_key")
        worst = {"plan_failed": self.failed, "answers_differ": len(self.answers) - 1,
                 "placement_mismatch": 0,
                 "unplaced_at_count": 0, "count_not_least": 0}
        ref_cache = {}
        for ok, c, got in self.kept:
            if not ok:
                continue
            if c not in ref_cache:
                pl = reference.schedule(scn.with_new_nodes(c), scn.classes, [], seq,
                                        scn.taint_keys, zone_key, precision)
                want = reference.counts(len(scn.tables.names) + c, len(scn.classes), [], seq, pl)
                below = 0
                if c > 0 and not reference.exceeds_capacity(scn.with_new_nodes(c - 1),
                                                            scn.classes, seq):
                    pl2 = reference.schedule(scn.with_new_nodes(c - 1), scn.classes, [], seq,
                                             scn.taint_keys, zone_key, precision)
                    below = int((pl2 >= 0).all())
                ref_cache[c] = (want, int((pl < 0).sum()), below)
            want, unplaced, below = ref_cache[c]
            worst["placement_mismatch"] = max(
                worst["placement_mismatch"], int(np.abs(got - want).sum()))
            worst["unplaced_at_count"] = max(worst["unplaced_at_count"], unplaced)
            worst["count_not_least"] = max(worst["count_not_least"], below)
        return {k: (v, 0) for k, v in worst.items()}
