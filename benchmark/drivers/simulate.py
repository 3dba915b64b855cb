"""One-shot ``scheduler.core.simulate`` calls on the TPU engine, back to
back, over a cluster with running pods (traffic kind "simulate").

Every simulate places the same pods under fresh Deployment names, so
no identity cache of the program serves a repeat; the program's memos
are cleared between calls, as its docs ask of embedders. Checked: the
placements of a seeded sample of the window's simulates, per node and
class, and their unscheduled counts, against the reference.
"""

from __future__ import annotations

import numpy as np

from benchmark import loop, reference
from benchmark.scenario import CLASS_LABEL, class_of, scaled

KEEP = 4


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
        self.keep = loop.Reservoir(KEEP, seed)
        self.salt = f"{seed % 1000003:06d}"

    def setup(self) -> None:
        self.cluster = self.scn.cluster()
        self.op(-1)  # compiles, or loads from the cache, every shape

    def op(self, i: int):
        from open_simulator_tpu.models.decode import ResourceTypes
        from open_simulator_tpu.scheduler.core import AppResource, simulate
        from open_simulator_tpu.utils.memo import clear_all_memos

        scn = self.scn
        res = ResourceTypes()
        res.deployments = [
            scn.deployment(c, r, f"{scn.classes[c].template}-{self.salt}-{i + 1}")
            for c, r in self.entries
        ]
        out = simulate(self.cluster, [AppResource(f"sim-{i + 1}", res)], engine="tpu")
        clear_all_memos()
        return out

    def window(self, seconds: float) -> dict:
        self.rec = loop.run_window(seconds, self.op, self.keep)
        return self.rec

    def metrics(self) -> dict:
        return {"sim_pods_per_s": self.pods_per_op * self.rec["ops"] / self.rec["elapsed_s"]}

    def context(self) -> dict:
        return {"kind": "simulate", **self.rec}

    def attempted_failed(self):
        return self.rec["ops"], 0  # a simulate that raises ends the run

    def release(self) -> None:
        scn = self.scn
        self.kept = [
            (
                len(res.unscheduled_pods),
                loop.program_counts(res.node_status, scn.tables.names,
                                    scn.class_index, CLASS_LABEL),
            )
            for _, res in self.keep.items
        ]
        self.keep.items = []
        self.cluster = None

    def check(self, precision: str = "high") -> dict:
        scn = self.scn
        seq = scn.ordered(self.entries)
        pl = reference.schedule(scn.tables, scn.classes, scn.bound, seq, scn.taint_keys,
                                scn.config["nodes"].get("zone_key"), precision)
        want = reference.counts(len(scn.tables.names), len(scn.classes), scn.bound, seq, pl)
        unplaced = int((pl < 0).sum())
        mismatch = max((int(np.abs(got - want).sum()) for _, got in self.kept), default=0)
        gap = max((abs(u - unplaced) for u, _ in self.kept), default=0)
        return {"placement_mismatch": (mismatch, 0), "unscheduled_gap": (gap, 0)}
