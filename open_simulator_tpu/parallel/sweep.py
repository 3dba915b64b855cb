"""Batched capacity-planning sweep over a TPU device mesh.

The reference's capacity loop is interactive: guess a node count, re-run
the whole simulation, ask the user (pkg/apply/apply.go:186-239). Here
every candidate count is one scenario of a single batched computation:

- the cluster is padded with `max_count` copies of the candidate node
  spec (named `simon-%02d` with the `simon/new-node` label, mirroring
  newFakeNodes, apply.go:288-306)
- scenario s enables the first s new nodes via a node-validity mask and
  drops daemonset pods that belong to disabled nodes via a pod-activity
  mask (the reference regenerates them per run)
- `vmap(run_scan_masked)` evaluates all scenarios at once; over a
  `jax.sharding.Mesh` the scenario axis is sharded across devices via
  `jit` with NamedSharding in_shardings (probe_many below) — scenarios
  are independent, so XLA's only communication is the result gather
  (this is the "distributed backend": XLA collectives over ICI, not a
  port of anything — the reference is single-process)

Returns per-scenario unscheduled counts and cluster utilization, from
which the planner picks the minimal feasible count
(satisfyResourceSetting caps, apply.go:611-697).
"""

from __future__ import annotations

import copy
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from ..models import workloads as wl
from ..models.decode import ResourceTypes
from ..models.validation import InputError
from ..scheduler.core import AppResource
from ..scheduler.oracle import Oracle

from ..runtime.guard import run_chunked, run_laddered

# pod not present in this scenario. Duplicates the ops/scan.py and
# ops/pallas_scan.py sentinel because importing either here would pull
# jax in at module-import time; CapacitySweep.__init__ asserts the
# three stay equal.
INACTIVE = -2


class PrioritySignalError(InputError):
    """Raised when a batched sweep is asked to plan a priority-bearing
    workload: the scan cannot model PrioritySort/preemption, and a
    silent non-preemptive plan would diverge from simulate() on the
    same input. Callers (apply/applier.py) catch this and fall back to
    the serial escalation loop, whose simulate() handles priority."""


# The PR-1 sweep-local OOM machinery (_is_oom / halving-retry /
# serial-fallback executor and its _OOM_INJECT test hook) moved to
# runtime/guard.py (run_chunked) so the sweep, chaos, and defrag paths
# share one audited degradation ladder.


@dataclass
class SweepResult:
    counts: List[int]
    unscheduled: np.ndarray  # [Sc] number of unschedulable (active) pods
    cpu_util: np.ndarray  # [Sc] percent
    mem_util: np.ndarray  # [Sc] percent
    placements: np.ndarray  # [Sc, P] node index / -1 / -2(inactive)
    pods: List[dict]
    node_names: List[str]
    vg_util: Optional[np.ndarray] = None  # [Sc] percent (0 when no VGs)


@dataclass
class ProbeResult:
    """One capacity scenario, evaluated by a single masked scan."""

    count: int
    unscheduled: int
    cpu_util: float
    mem_util: float
    vg_util: float
    placements: np.ndarray  # [P] node index / -1 / -2(inactive)


def _probe_to_record(res: ProbeResult) -> dict:
    """JSON-serializable journal record of one probe (runtime/journal)."""
    return {
        "count": int(res.count),
        "unscheduled": int(res.unscheduled),
        "cpuUtil": float(res.cpu_util),
        "memUtil": float(res.mem_util),
        "vgUtil": float(res.vg_util),
        "placements": [int(x) for x in np.asarray(res.placements)],
    }


def _probe_from_record(rec: dict) -> ProbeResult:
    return ProbeResult(
        count=int(rec["count"]),
        unscheduled=int(rec["unscheduled"]),
        cpu_util=float(rec["cpuUtil"]),
        mem_util=float(rec["memUtil"]),
        vg_util=float(rec["vgUtil"]),
        placements=np.asarray(rec["placements"], dtype=np.int64),
    )


def _new_nodes(spec: dict, count: int) -> List[dict]:
    out = []
    for i in range(count):
        node = wl.make_valid_node(copy.deepcopy(spec), f"{wl.NEW_NODE_NAME_PREFIX}-{i:02d}")
        node["metadata"].setdefault("labels", {})[wl.LABEL_NEW_NODE] = ""
        out.append(node)
    return out


def _daemonset_target(pod: dict) -> Optional[str]:
    """The node a daemonset pod is pinned to via its matchFields term."""
    aff = ((pod.get("spec") or {}).get("affinity") or {}).get("nodeAffinity") or {}
    required = aff.get("requiredDuringSchedulingIgnoredDuringExecution") or {}
    for term in required.get("nodeSelectorTerms") or []:
        for f in term.get("matchFields") or []:
            if f.get("key") == "metadata.name" and f.get("operator") == "In":
                values = f.get("values") or []
                if values:
                    return values[0]
    return None


class CapacitySweep:
    """Encode-once / probe-many capacity search.

    The cluster is padded with `max_count` candidate nodes exactly once;
    every probe is a single masked scan with a different node-validity
    mask — same shapes, so XLA compiles one executable for every count
    (the reference re-runs the whole simulation per guess,
    pkg/apply/apply.go:186-239).
    """

    def __init__(
        self,
        cluster: ResourceTypes,
        apps: List[AppResource],
        new_node_spec: Optional[dict],
        max_count: int,
        use_greed: bool = False,
        score_weights=None,
        share_pods_from: "Optional[CapacitySweep]" = None,
    ):
        from ..ops.encode import (
            encode_batch,
            encode_cluster,
            encode_dynamic,
            features_of_batch,
            to_scan_static,
            to_scan_state,
        )
        from ..utils.trace import phase

        self.max_count = max_count if new_node_spec is not None else 0
        with phase("sweep/pad"):
            padded = cluster.copy()
            padded.nodes = list(padded.nodes) + _new_nodes(new_node_spec, self.max_count)

        # Build oracle at full padding; generate the full pod sequence
        # the serial path would see (cluster pods, then apps in order).
        # A multi-spec what-if (probe_plan_multi) reuses a sibling
        # sweep's expanded pod list when expansion is provably
        # spec-INDEPENDENT: the only node-dependent expansions are
        # daemonsets (one pod per node) and greed_sort ordering. The
        # shared dicts follow the same repeated-replay contract as one
        # sweep replayed at several counts (had_node_name below).
        if (
            share_pods_from is None
            or use_greed
            or padded.daemon_sets
            or any(app.resource.daemon_sets for app in apps)
            or share_pods_from.max_count != self.max_count
            or share_pods_from.n_base != len(cluster.nodes)
        ):
            share_pods_from = None
        # replays MUTATE pod dicts (bind writes nodeName/phase), so a
        # multi-spec caller must give each sweep its own copies before
        # replaying (applier.probe_plan_multi checks this flag)
        self.pods_shared = share_pods_from is not None
        with phase("sweep/expand"):
            self.oracle = Oracle(padded.nodes)
            if share_pods_from is not None:
                # expansion (and its priority/plugin checks) shared
                pods, groups = share_pods_from.pods, share_pods_from.groups
            else:
                from ..scheduler.preemption import (
                    batch_priorities,
                    build_priority_resolver,
                )
                from ..scheduler.queues import expand_apps

                index = wl.ExpandIndex()
                pods = wl.expand_pods(padded, padded.nodes, index)
                resolver = build_priority_resolver(cluster.priority_classes)
                # the order the authoritative serial run uses
                # (scheduler/core.py schedule_app): greed_sort ignores
                # simon new nodes, so max-count padding and the
                # per-count serial cluster sort pods identically. A
                # custom QueueSort plugin is not honoured here
                app_pods, app_groups, prios = expand_apps(
                    apps, padded.nodes, resolver=resolver, use_greed=use_greed
                )
                pods.extend(app_pods)
                groups = wl.join_groups(index.groups(), app_groups)
                if prios.any() or batch_priorities(index.firsts, resolver).any():
                    raise PrioritySignalError(
                        "workload carries priority/priorityClassName; the "
                        "batched scan has no priority/preemption semantics — "
                        "use the serial engine (scheduler/core.py falls back "
                        "automatically)"
                    )
                if self.oracle.registry.needs_serial:
                    raise PrioritySignalError(
                        "a registered plugin defines permit() or a stateful "
                        "hook (reserve/prebind); the batched scan cannot "
                        "honor per-pod host callbacks — use the serial "
                        "engine (scheduler/core.py falls back automatically)"
                    )
        self.pods = pods
        # content groups of the expansion: the batch encodes once per
        # template (ops/encode.py encode_batch)
        self.groups = groups
        self.n = len(padded.nodes)
        self.n_base = self.n - self.max_count

        with phase("sweep/encode"):
            self.cluster_enc = encode_cluster(self.oracle)
            self.batch = encode_batch(self.oracle, self.cluster_enc, pods, groups)
            self.dyn = encode_dynamic(self.oracle, self.cluster_enc)
            self.static = to_scan_static(self.cluster_enc, self.batch)
            self.init = to_scan_state(self.dyn, self.batch)
            # derive features host-side: inside a jit/vmap trace
            # features_of would fall back to the ungated ALL_FEATURES scan
            self.features = features_of_batch(
                self.cluster_enc, self.batch, weights=score_weights
            )

        # which pods arrived with spec.nodeName, recorded BEFORE any
        # replay binds pods (replay_scenario writes nodeName into these
        # shared pod dicts; a later replay must not mistake a previous
        # replay's binding for an original pin)
        with phase("sweep/index"):
            self.had_node_name = np.array(
                [bool((p.get("spec") or {}).get("nodeName")) for p in pods], dtype=bool
            )
            # daemonset pods of disabled candidate nodes are inactive in
            # that scenario (the reference regenerates them per run)
            self._ds_target = np.full(len(pods), -1, dtype=np.int64)
            name_to_idx = self.oracle.node_index
            for p_i, pod in enumerate(pods):
                target = _daemonset_target(pod)
                if target is not None and target in name_to_idx:
                    self._ds_target[p_i] = name_to_idx[target]
        self._probe_jit = None
        self._many_jit = None
        # process-wide mesh (parallel/mesh.py configure/current_mesh,
        # the --mesh flag): the layout planner decides PER REQUEST
        # whether to shard the scenario axis (probe_many /
        # probe_scenarios) or the node axis (single probes on big
        # clusters) across it; None = the single-device ladder
        from . import mesh as mesh_mod

        self.mesh = mesh_mod.current_mesh()
        self._node_plan = None  # padded node-sharded state, built lazily
        self._mesh_retired = False  # a mesh rung fault retires the mesh
        # optional resumable journal (runtime/journal.py): probe()
        # serves journaled counts without touching the device and
        # appends every fresh result (attach_journal)
        self.journal = None
        # fused single-kernel fast path (ops/pallas_scan.py); None when
        # the batch uses machinery outside its scope or the backend is
        # not a real TPU (the interpreter would crawl at bench scale)
        from ..ops import pallas_scan, scan as scan_ops

        assert INACTIVE == scan_ops.INACTIVE == pallas_scan.INACTIVE

        with phase("sweep/kernel-plan"):
            self._pallas_plan = (
                pallas_scan.build_plan(
                    self.cluster_enc, self.batch, self.dyn, self.features,
                    weights=self.features.weights,
                )
                if pallas_scan.should_use()
                else None
            )
        from ..utils.trace import GLOBAL

        GLOBAL.note(
            "sweep-kernel",
            "pallas"
            if self._pallas_plan is not None
            else f"xla-scan ({pallas_scan.fallback_reason()})",
        )

    # -- masks -------------------------------------------------------------

    def node_valid(self, count: int) -> np.ndarray:
        valid = np.ones(self.n, dtype=bool)
        valid[self.n_base + count :] = False
        return valid

    def pod_active(self, valid: np.ndarray) -> np.ndarray:
        active = np.ones(len(self.pods), dtype=bool)
        tgt = self._ds_target
        has_tgt = tgt >= 0
        active[has_tgt] = valid[tgt[has_tgt]]
        return active

    # -- the compiled scenario ---------------------------------------------

    def _scenario(self, valid, active):
        import jax.numpy as jnp

        return self._scenario_impl(
            valid, active, jnp.asarray(self.batch.pinned_node), self.features
        )

    def _scenario_impl(self, valid, active, pinned, features):
        import jax.numpy as jnp

        from ..ops import scan as scan_ops

        placements, final = scan_ops.run_scan_masked(
            self.static,
            self.init,
            jnp.asarray(self.batch.class_of_pod),
            pinned,
            valid,
            active,
            features=features,
        )
        unsched = jnp.sum(placements == -1)
        cpu_util, mem_util, vg_util = self._utilization(valid, final)
        return placements, unsched, cpu_util, mem_util, vg_util

    def _utilization(self, valid, final):
        return _utilization_impl(self.static, valid, final)

    def attach_journal(self, journal):
        """Serve journaled probes without device work; append fresh
        ones (runtime/journal.py, `--journal` / `--resume`)."""
        self.journal = journal

    def probe(self, count: int) -> ProbeResult:
        """Evaluate one candidate count (one masked scan), through the
        engine ladder (runtime/guard.py): the fused Pallas kernel when
        a plan exists, the jitted XLA scan, and — after a classified
        device fault at each rung — the serial host oracle. A Pallas
        rung failure retires the plan so later probes skip it. Counts
        already in the attached journal never touch the device."""
        if self.journal is not None:
            cached = self.journal.get_probe(count)
            if cached is not None:
                return _probe_from_record(cached)
        res = self._probe_device(count)
        if self.journal is not None:
            self.journal.record_probe(_probe_to_record(res))
        return res

    def _probe_device(self, count: int) -> ProbeResult:
        from ..obs.costs import COSTS
        from ..obs.ledger import LEDGER
        from . import mesh as mesh_mod

        valid = self.node_valid(count)
        steps = []
        if self._pallas_plan is not None:
            steps.append(("pallas", lambda: self._probe_pallas(count, valid)))
        # node-axis mesh rung: ONE scenario over a cluster the planner
        # says is too big (or predicted not to fit) on one device —
        # each device scores its node shard, the winner reduces
        # globally (parallel/mesh.py). A classified fault retires the
        # rung for this sweep and the ladder continues unsharded.
        if self._pallas_plan is None and not self._mesh_retired:
            # site "sweep_probe": the single-device probe jit whose
            # compiled records say whether one device can hold it
            layout = mesh_mod.plan_layout(
                "sweep_probe", mesh=self.mesh, n_scenarios=1,
                n_nodes=self.n,
                sample=bool(getattr(self.features, "sample", False)),
            )
            if layout.axis == "node":
                steps.append(
                    ("mesh-scan", lambda: self._probe_mesh(count, valid))
                )
        steps.append(("xla-scan", lambda: self._probe_xla(count, valid)))
        steps.append(("serial-oracle", lambda: self._probe_serial(count, valid)))

        def on_downgrade(rung, _e):
            if rung == "pallas":
                self._pallas_plan = None  # retire the dead rung
            if rung == "mesh-scan":
                self._mesh_retired = True
                self._node_plan = None

        # predictive rung gate: once a rung's shape has compiled, the
        # memory ledger can veto re-dispatching it into a device that
        # no longer has room — the doomed dispatch is skipped instead
        # of caught (no-op until the backend/env reports a budget)
        predictor = LEDGER.rung_predictor(
            {"xla-scan": lambda: COSTS.estimate_bytes("sweep_probe")}
        )
        return run_laddered(
            steps, label="sweep-probe", on_downgrade=on_downgrade,
            predictor=predictor,
        )

    def _probe_mesh(self, count: int, valid) -> ProbeResult:
        """One capacity probe through the node-axis-sharded scan: the
        padded shard state is built once per sweep (NodeShardPlan), so
        repeated probes pay only the masks' transfer."""
        from ..utils.trace import phase
        from . import mesh as mesh_mod

        if self._node_plan is None:
            self._node_plan = mesh_mod.NodeShardPlan(
                self.mesh, self.static, self.init,
                self.batch.class_of_pod, self.batch.pinned_node,
                self.features,
            )
        with phase("sweep/probe"):
            pl, unsched, cpu, mem, vg = self._node_plan.run(
                valid, self.pod_active(valid)
            )
        return ProbeResult(
            count=count, unscheduled=unsched, cpu_util=cpu,
            mem_util=mem, vg_util=vg, placements=pl,
        )

    def _probe_pallas(self, count: int, valid) -> ProbeResult:
        from ..ops import pallas_scan
        from ..utils.trace import phase

        with phase("sweep/probe"):
            placements, final = pallas_scan.run_scan_pallas(
                self._pallas_plan,
                self.batch.class_of_pod,
                self.pod_active(valid),
                valid,
                pinned=self.batch.pinned_node,
            )
        return self._pallas_result(count, valid, placements, final)

    def _probe_xla(self, count: int, valid) -> ProbeResult:
        import jax
        import jax.numpy as jnp

        from ..utils.trace import phase

        if self._probe_jit is None:
            from ..obs import profile

            self._probe_jit = profile.instrument_jit(
                jax.jit(self._scenario), "sweep_probe"
            )
        with phase("sweep/probe"):
            placements, unsched, cpu, mem, vg = self._probe_jit(
                jnp.asarray(valid), jnp.asarray(self.pod_active(valid))
            )
            placements = np.asarray(placements)
        return ProbeResult(
            count=count,
            unscheduled=int(unsched),
            cpu_util=float(cpu),
            mem_util=float(mem),
            vg_util=float(vg),
            placements=placements,
        )

    def _probe_serial(self, count: int, valid) -> ProbeResult:
        """Last ladder rung: the deterministic host oracle, no device."""
        active = self.pod_active(valid)
        placements, _reasons = self.serial_scenario(valid, active)
        pl, unsched, cpu, mem, vg = self._host_scenario_stats(valid, placements)
        return ProbeResult(
            count=count,
            unscheduled=int(unsched),
            cpu_util=float(cpu),
            mem_util=float(mem),
            vg_util=float(vg),
            placements=pl,
        )

    def _pallas_result(self, count, valid, placements, final) -> ProbeResult:
        # same utilization arithmetic as _scenario, on the host
        v = valid[: self.n]
        alloc_c = np.asarray(self.cluster_enc.alloc_mcpu)
        alloc_m = np.asarray(self.cluster_enc.alloc_mem)
        denom_c = max(int(alloc_c[v].sum()), 1)
        denom_m = max(int(alloc_m[v].sum()), 1)
        cpu_util = 100.0 * float(final["used_mcpu"][v].sum()) / denom_c
        mem_util = 100.0 * float(final["used_mem"][v].sum()) / denom_m
        vg_cap = np.asarray(self.cluster_enc.vg_cap)
        # final VG usage exported by the kernel (storage batches ride
        # the Pallas path since r5); storage-free batches never grow
        # it, so the init state is exact for them
        vg_used = np.asarray(final.get("vg_used", self.dyn.vg_used))
        denom_vg = max(int(vg_cap[v].sum()), 1)
        vg_util = 100.0 * float(vg_used[v].sum()) / denom_vg
        return ProbeResult(
            count=count,
            unscheduled=int((placements == -1).sum()),
            cpu_util=cpu_util,
            mem_util=mem_util,
            vg_util=vg_util,
            placements=placements,
        )

    def probe_pair(self, c1: int, c2: int):
        """Two candidate counts with ONE device sync: on the Pallas
        path both scans dispatch deferred and fetch stacked (the defrag
        batching pattern) — the per-sync latency is paid once.
        Falls back to two sequential probes on the XLA path."""
        if self._pallas_plan is None or (
            self.journal is not None
            and (
                self.journal.get_probe(c1) is not None
                or self.journal.get_probe(c2) is not None
            )
        ):
            # journaled counts must not ride the paired dispatch: probe()
            # serves them from the journal, so pairing would re-run them
            return self.probe(c1), self.probe(c2)
        from ..ops import pallas_scan
        from ..utils.trace import phase

        valids = [self.node_valid(c) for c in (c1, c2)]
        with phase("sweep/probe"):
            decoded = pallas_scan.run_scan_pallas_batch(
                self._pallas_plan,
                self.batch.class_of_pod,
                [
                    (self.pod_active(v), v, self.batch.pinned_node)
                    for v in valids
                ],
            )
        out = tuple(
            self._pallas_result(c, valid, placements, final)
            for c, valid, (placements, final) in zip((c1, c2), valids, decoded)
        )
        if self.journal is not None:
            for r in out:
                self.journal.record_probe(_probe_to_record(r))
        return out

    def probe_many(self, counts: List[int], mesh=None, budget=None) -> SweepResult:
        """Evaluate many counts batched (vmap; scenario-sharded over a
        device mesh when one is given). Chunked with OOM halving-retry
        (runtime/guard.py run_chunked): a scenario batch that exhausts
        device memory is split and retried, bottoming out in the
        deterministic serial oracle — every degradation trace-noted,
        never silent. `budget` halts between chunks (ExecutionHalted
        with the completed prefix attached)."""
        import jax
        import jax.numpy as jnp

        sc = len(counts)
        node_valid = np.stack([self.node_valid(c) for c in counts])
        pod_active = np.stack([self.pod_active(v) for v in node_valid])
        # ONE jitted vmap per sweep instance (JAX002: a fresh
        # jax.jit(...) per evaluate() chunk re-traced and re-compiled
        # every chunk). The mesh path reuses the same wrapper:
        # device_put commits the scenario axis to the NamedSharding and
        # jit compiles per observed input sharding ("computation
        # follows sharding"), so sharded and unsharded batches each
        # warm their own cache entry once.
        if self._many_jit is None:
            from ..obs import profile

            self._many_jit = profile.instrument_jit(
                jax.jit(jax.vmap(self._scenario)), "sweep_many",
                lead_argnum=0,
            )

        # layout planner: an explicit mesh argument wins (the historic
        # sweep_node_counts contract); otherwise the process-wide mesh
        # shards the scenario axis when the planner picks it
        from . import mesh as mesh_mod

        if mesh is None:
            layout = mesh_mod.plan_layout(
                "sweep_many", mesh=self.mesh, n_scenarios=sc,
                n_nodes=self.n,
                sample=bool(getattr(self.features, "sample", False)),
            )
            if layout.axis == "scenario":
                mesh = self.mesh
        n_dev = int(mesh.devices.size) if mesh is not None else 1

        def evaluate(lo, hi):
            nonlocal mesh
            if mesh is not None:
                try:
                    (valid_s, active_s), _rows = mesh_mod.shard_scenario_rows(
                        mesh, [node_valid[lo:hi], pod_active[lo:hi]]
                    )
                    out = self._many_jit(valid_s, active_s)
                    arrays = [np.asarray(o)[: hi - lo] for o in out]
                    return list(zip(*arrays))
                except (RuntimeError, MemoryError, OSError) as e:
                    from ..runtime.guard import try_downgrade

                    if not try_downgrade(
                        e, label="sweep", frm="mesh-scenario", to="xla-scan"
                    ):
                        raise
                    mesh = None
            out = self._many_jit(
                jnp.asarray(node_valid[lo:hi]), jnp.asarray(pod_active[lo:hi])
            )
            return list(zip(*(np.asarray(o) for o in out)))

        def serial_fallback(i):
            placements, _ = self.serial_scenario(node_valid[i], pod_active[i])
            return self._host_scenario_stats(node_valid[i], placements)

        from ..obs.costs import COSTS

        # estimator + shard count re-read per chunk (mid-run mesh
        # downgrade flips later chunks to full-size prediction)
        est_plain = COSTS.chunk_estimator("sweep_many")
        est_shard = COSTS.chunk_estimator("sweep_many", shards=n_dev)

        def estimate(lo, hi):
            return (est_shard if mesh is not None else est_plain)(lo, hi)

        rows = run_chunked(
            evaluate, sc, label="sweep", serial_fallback=serial_fallback,
            budget=budget, estimate=estimate,
            shards=lambda: n_dev if mesh is not None else 1,
        )
        placements, unsched, cpu_util, mem_util, vg_util = (
            np.stack([np.asarray(r[k]) for r in rows]) for k in range(5)
        )

        return SweepResult(
            counts=list(counts),
            unscheduled=unsched,
            cpu_util=cpu_util,
            mem_util=mem_util,
            placements=placements,
            pods=self.pods,
            node_names=[ns.name for ns in self.oracle.nodes],
            vg_util=vg_util,
        )

    # -- serial (host-oracle) scenario evaluation ---------------------------

    def serial_scenario(self, valid, active, pinned=None, pins_first=False):
        """Deterministic host-side evaluation of ONE masked scenario
        through the serial oracle (scheduler/oracle.py) — the sweep's
        last resort when even a single-scenario device batch exhausts
        memory, and the resilience engine's independent confirmation
        path (an N+K verdict is only trusted after one sampled outage
        re-simulates serially to the same answer).

        `pinned[p]` >= 0 force-binds the pod to that sweep node index
        (committed placements / original spec.nodeName); -1 schedules
        through the full filter+score cycle. Defaults to the batch's
        original pins. `pins_first` commits every pinned pod before any
        free pod schedules — the chaos model's two-pass order
        (_scenario_pinned_impl); the default interleaves in pod order like
        the single-pass capacity scan. Returns (placements[P] in SWEEP
        node indices with the scan's -1/-2 conventions,
        {pod_index: reason} for unscheduled pods)."""
        from ..scheduler.oracle import Oracle

        if pinned is None:
            pinned = np.asarray(self.batch.pinned_node)
        valid = np.asarray(valid)
        active = np.asarray(active)
        kept = [i for i in range(self.n) if valid[i]]
        oracle = Oracle(
            [self.oracle.nodes[i].node for i in kept],
            score_weights=self.features.weights,
        )
        local_of = {sweep_i: local_i for local_i, sweep_i in enumerate(kept)}
        sweep_index = self.oracle.node_index
        placements = np.full(len(self.pods), -1, dtype=np.int64)
        reasons: dict = {}

        def handle(p_i, pod, pins_only):
            if not active[p_i]:
                placements[p_i] = INACTIVE
                return
            pin = int(pinned[p_i])
            if pins_only is not None and pins_only != (pin >= 0):
                return
            # repeated-replay contract (replay_scenario): a previous
            # replay may have bound this shared dict — only original
            # spec.nodeName pins survive into this scenario
            if not self.had_node_name[p_i]:
                (pod.get("spec") or {}).pop("nodeName", None)
                (pod.get("status") or {}).pop("phase", None)
            if pin >= 0:
                if not valid[pin]:
                    # pinned to a masked-out node: does not exist in
                    # this scenario (scan INACTIVE convention)
                    placements[p_i] = INACTIVE
                    return
                if self.had_node_name[p_i]:
                    # original spec.nodeName: admit exactly like the
                    # replay (GPU-index annotations honored)
                    oracle.place_existing_pod(pod)
                else:
                    oracle._reserve_and_bind(pod, oracle.nodes[local_of[pin]])
                placements[p_i] = pin
                return
            name, reason = oracle.schedule_pod(pod)
            if name is None:
                placements[p_i] = -1
                reasons[p_i] = reason
            else:
                placements[p_i] = sweep_index[name]

        if pins_first:
            for p_i, pod in enumerate(self.pods):
                handle(p_i, pod, pins_only=True)
            for p_i, pod in enumerate(self.pods):
                if active[p_i] and int(pinned[p_i]) < 0:
                    handle(p_i, pod, pins_only=False)
        else:
            for p_i, pod in enumerate(self.pods):
                handle(p_i, pod, pins_only=None)
        return placements, reasons

    def _host_scenario_stats(self, valid, placements):
        """The (placements, unscheduled, cpu/mem/vg utilization) tuple
        of _scenario, recomputed on the host from serial placements —
        same arithmetic, aggregate form (committed requests add onto
        the encoded base usage; placements only land on valid nodes)."""
        b, d, c_enc = self.batch, self.dyn, self.cluster_enc
        v = np.asarray(valid)
        placed = np.asarray(placements) >= 0
        cls = np.asarray(b.class_of_pod)[placed]
        used_c = int(d.used_mcpu[v].sum()) + int(b.req_mcpu[cls].sum())
        used_m = int(d.used_mem[v].sum()) + int(b.req_mem[cls].sum())
        used_v = int(d.vg_used[v].sum()) + int(b.lvm_sizes[cls].sum())
        denom_c = max(int(c_enc.alloc_mcpu[v].sum()), 1)
        denom_m = max(int(c_enc.alloc_mem[v].sum()), 1)
        denom_v = max(int(c_enc.vg_cap[v].sum()), 1)
        return (
            np.asarray(placements),
            np.int64((np.asarray(placements) == -1).sum()),
            np.float64(100.0 * used_c / denom_c),
            np.float64(100.0 * used_m / denom_m),
            np.float64(100.0 * used_v / denom_v),
        )

    def probe_scenarios(self, node_valid, pod_active, pinned, budget=None,
                        site: str = "chaos"):
        """Batched masked scans with PER-SCENARIO pin vectors — the
        fault-injection substrate (resilience/chaos.py) and the
        timeline stepper's window entry point (timeline/stepper.py:
        each policy's window is one row). Each row of `node_valid`
        [Sc, N] / `pod_active` [Sc, P] / `pinned` [Sc, P] is one
        scenario; rides the same chunked executor as probe_many (OOM
        halving-retry, serial-oracle floor). Returns (placements
        [Sc, P], unscheduled [Sc], cpu_util [Sc], mem_util [Sc],
        vg_util [Sc]) as numpy arrays. `site` names the
        instrumented-jit counter family (obs) so each caller's
        dispatches stay attributable.

        Runs on the XLA masked scan (the Pallas plan is compiled for
        the batch's original pin feature set); chaos batches are
        scenario-bound, not pod-throughput-bound, so this is the
        latency-appropriate path. With a process-wide mesh the layout
        planner shards the scenario axis across it (rows are
        independent; the only communication is the result gather) via
        a per-site ``mesh_<site>`` jit family, so sharded dispatch and
        injection seams (``jit.mesh_*``) stay separately attributable;
        a classified device fault on the sharded path degrades to the
        unsharded ladder, trace-noted."""
        import jax.numpy as jnp

        from . import mesh as mesh_mod

        node_valid = np.asarray(node_valid)
        pod_active = np.asarray(pod_active)
        pinned = np.asarray(pinned)
        sc = node_valid.shape[0]
        site_jit = _scenario_rows_jit(site)
        cls = jnp.asarray(self.batch.class_of_pod)
        layout = mesh_mod.plan_layout(
            f"{site}_sweep", mesh=self.mesh, n_scenarios=sc, n_nodes=self.n,
            sample=bool(getattr(self.features, "sample", False)),
        )
        mesh = self.mesh if layout.axis == "scenario" else None
        n_dev = int(mesh.devices.size) if mesh is not None else 1
        mesh_jit = _scenario_rows_jit(f"mesh_{site}") if mesh is not None else None

        def evaluate(lo, hi):
            nonlocal mesh
            if mesh is not None:
                try:
                    (valid_s, active_s, pin_s), _rows = (
                        mesh_mod.shard_scenario_rows(
                            mesh,
                            [node_valid[lo:hi], pod_active[lo:hi], pinned[lo:hi]],
                        )
                    )
                    out = mesh_jit(
                        self.static, self.init, cls,
                        valid_s, active_s, pin_s, self.features,
                    )
                    return list(zip(*(np.asarray(o)[: hi - lo] for o in out)))
                except (RuntimeError, MemoryError, OSError) as e:
                    from ..runtime.guard import try_downgrade

                    if not try_downgrade(
                        e, label=site, frm="mesh-scenario", to="xla-scan"
                    ):
                        raise
                    mesh = None
            out = site_jit(
                self.static,
                self.init,
                cls,
                jnp.asarray(node_valid[lo:hi]),
                jnp.asarray(pod_active[lo:hi]),
                jnp.asarray(pinned[lo:hi]),
                self.features,
            )
            return list(zip(*(np.asarray(o) for o in out)))

        def serial_fallback(i):
            placements, _ = self.serial_scenario(
                node_valid[i], pod_active[i], pinned[i], pins_first=True
            )
            return self._host_scenario_stats(node_valid[i], placements)

        from ..obs.costs import COSTS

        # estimator + shard count re-read per chunk: a mid-run mesh
        # downgrade inside evaluate() must flip later chunks back to
        # full-size single-device prediction arithmetic
        est_plain = COSTS.chunk_estimator(f"{site}_sweep")
        est_shard = COSTS.chunk_estimator(f"{site}_sweep", shards=n_dev)

        def estimate(lo, hi):
            return (est_shard if mesh is not None else est_plain)(lo, hi)

        rows = run_chunked(
            evaluate, sc, label=site, serial_fallback=serial_fallback,
            budget=budget, estimate=estimate,
            shards=lambda: n_dev if mesh is not None else 1,
        )
        placements = np.stack([np.asarray(r[0]) for r in rows])
        unsched = np.array([int(r[1]) for r in rows], dtype=np.int64)
        cpu = np.array([float(r[2]) for r in rows])
        mem = np.array([float(r[3]) for r in rows])
        vg = np.array([float(r[4]) for r in rows])
        return placements, unsched, cpu, mem, vg

    # -- resource lower bound ----------------------------------------------

    def lower_bound(self, max_cpu: int = 100, max_mem: int = 100, max_vg: int = 100) -> int:
        """Smallest count not ruled out by aggregate resource totals and
        utilization caps. Any count below it either leaves pods
        unschedulable (sum of requests exceeds sum of allocatable) or
        violates a cap, so the scheduling search can start here. Purely
        arithmetic — no scan."""
        from ..utils.trace import phase

        with phase("sweep/lower-bound"):
            b, c_enc, d = self.batch, self.cluster_enc, self.dyn
            cls = b.class_of_pod
            req = {
                "mcpu": b.req_mcpu[cls].astype(np.int64),
                "mem": b.req_mem[cls].astype(np.int64),
                "eph": b.req_eph[cls].astype(np.int64),
                "pods": np.ones(len(self.pods), dtype=np.int64),
                "vg": b.lvm_sizes[cls].sum(axis=1).astype(np.int64),
            }
            alloc = {
                "mcpu": c_enc.alloc_mcpu,
                "mem": c_enc.alloc_mem,
                "eph": c_enc.alloc_eph,
                "pods": c_enc.alloc_pods,
                "vg": c_enc.vg_cap.sum(axis=1),
            }
            base_used = {
                "mcpu": int(d.used_mcpu.sum()),
                "mem": int(d.used_mem.sum()),
                "eph": int(d.used_eph.sum()),
                "pods": int(d.pod_cnt.sum()),
                "vg": int(d.vg_used.sum()),
            }
            # every count 0..max_count at once, as prefix sums over the
            # candidate axis: slot j + 1 holds what candidate n_base + j
            # brings. A pod counts at every count when its _ds_target is
            # below n_base (-1 included), else from its candidate on
            nb, mc = self.n_base, self.max_count
            tgt = self._ds_target
            on_new = tgt >= nb
            slot = tgt[on_new] - nb + 1

            def used_at(r):
                added = np.zeros(mc + 1, dtype=np.int64)
                np.add.at(added, slot, req[r][on_new])
                return base_used[r] + int(req[r][~on_new].sum()) + np.cumsum(added)

            def alloc_at(r):
                a = np.asarray(alloc[r], dtype=np.int64)
                return int(a[:nb].sum()) + np.concatenate(([0], np.cumsum(a[nb:])))

            used = {r: used_at(r) for r in req}
            total = {r: alloc_at(r) for r in alloc}
            ok = np.ones(mc + 1, dtype=bool)
            for r in ("mcpu", "mem", "eph", "pods"):
                ok &= used[r] <= total[r]
            for r, cap in (("mcpu", max_cpu), ("mem", max_mem), ("vg", max_vg)):
                u, t = used[r], total[r]
                has = t != 0
                share = np.divide(u, t, out=np.zeros(mc + 1), where=has) * 100
                over = has & (np.trunc(share) > cap)
                # float64 division rounds like Python's int division
                # only while both ints are below 2**53
                for c in np.flatnonzero(has & ((u >= 2**53) | (t >= 2**53))):
                    over[c] = int(int(u[c]) / int(t[c]) * 100) > cap
                ok &= ~over
            return int(np.argmax(ok)) if ok.any() else mc

    # -- minimal-count search ----------------------------------------------

    def estimate_extra(self, res: ProbeResult) -> int:
        """How many more candidate nodes the unscheduled pods of this
        probe need by aggregate request (a Newton-style step for the
        escalation: usually lands within a node or two of the true
        minimum even when taints/selectors make the global lower bound
        loose)."""
        mask = res.placements == -1
        if not mask.any() or self.max_count == 0:
            return 1
        cls = self.batch.class_of_pod[np.asarray(mask)]
        b = self.batch
        new_i = self.n_base  # all candidate nodes share the spec
        extra = 1
        for req_v, alloc_v in (
            (b.req_mcpu[cls], self.cluster_enc.alloc_mcpu[new_i]),
            (b.req_mem[cls], self.cluster_enc.alloc_mem[new_i]),
            (b.req_eph[cls], self.cluster_enc.alloc_eph[new_i]),
            (np.ones(len(cls), dtype=np.int64), self.cluster_enc.alloc_pods[new_i]),
        ):
            need = int(req_v.sum())
            alloc = int(alloc_v)
            if alloc > 0 and need > 0:
                extra = max(extra, -(-need // alloc))
        return extra

    def _search_gen(self, feasible, start: int = 0, widen: bool = False):
        """The min-count search as a COROUTINE: yields lists of counts
        to probe, receives {count: ProbeResult}, and returns the best
        result (or None) via StopIteration. Extracting the control flow
        from the probe transport lets find_min_count fulfil requests
        one spec at a time while find_min_count_multi batches the
        requests of MANY specs into one device sync per round.

        Search shape (unchanged from r3/r4): probe `start`; on failure
        escalate by the unscheduled-request estimate (with a doubling
        backstop) — asking for (hi-1, hi) together on the Pallas path
        since the estimate usually lands exactly — then bisect the
        bracket, confirming hi-1 first. Monotonicity (more nodes never
        schedule fewer pods) is asserted by tests/test_capacity.py."""
        probes: dict = {}

        probes.update((yield [start]))
        res = probes[start]
        if feasible(res):
            return res
        # grow bracket: (lo known-infeasible, hi candidate]
        lo, escalations = start, 0
        while True:
            step = max(self.estimate_extra(probes[lo]), 1 << escalations)
            hi = min(lo + step, self.max_count)
            if (
                hi - lo > 1
                and hi not in probes
                and hi - 1 not in probes
                and self._pallas_plan is not None
            ):
                # hi-1 is usually the bisection's very next question:
                # ask for both in one round
                probes.update((yield [hi - 1, hi]))
            elif hi not in probes:
                probes.update((yield [hi]))
            res = probes[hi]
            if feasible(res):
                break
            lo = hi
            if hi == self.max_count:
                return None  # infeasible even at max
            escalations += 1
        # bisect (lo infeasible, hi feasible]. In the MULTI driver
        # (widen=True) a small bracket probes every interior count in
        # one round instead of log2 sequential rounds — extra scans
        # are cheap at what-if scale and each saved round saves a
        # device round-trip; the single-spec path keeps pure bisection
        # (a 100k-pod capacity probe costs ~1s of scan, so extra
        # probes would dominate the saved latency)
        best = res
        lo_b, hi_b = lo, best.count
        if widen and 2 < hi_b - lo_b <= 16 and self._pallas_plan is not None:
            need = [c for c in range(lo_b + 1, hi_b) if c not in probes]
            if need:
                probes.update((yield need))
            for c in range(lo_b + 1, hi_b):
                if feasible(probes[c]):
                    return probes[c]
            return best
        if hi_b - lo_b > 1:
            c = hi_b - 1
            if c not in probes:
                probes.update((yield [c]))
            res = probes[c]
            if feasible(res):
                best, hi_b = res, c
            else:
                lo_b = c
        while hi_b - lo_b > 1:
            mid = (lo_b + hi_b) // 2
            if mid not in probes:
                probes.update((yield [mid]))
            res = probes[mid]
            if feasible(res):
                best, hi_b = res, mid
            else:
                lo_b = mid
        return best

    def _fulfill(self, req: List[int], on_probe=None) -> dict:
        """Probe the requested counts — paired into one device sync on
        the Pallas path when the search asks for two."""
        if len(req) == 2 and self._pallas_plan is not None:
            r1, r2 = self.probe_pair(req[0], req[1])
            out = {r1.count: r1, r2.count: r2}
        else:
            out = {c: self.probe(c) for c in req}
        if on_probe is not None:
            for r in out.values():
                on_probe(r)
        return out

    def find_min_count(
        self,
        feasible,
        start: int = 0,
        on_probe=None,
        budget=None,
    ) -> Optional[ProbeResult]:
        """Smallest count whose probe satisfies `feasible(ProbeResult)`
        (one spec; see _search_gen for the search shape). `budget` is
        checked between probe rounds (the search's safe boundary); on
        halt the raised ExecutionHalted carries a machine-readable
        partial payload: every completed probe and the best feasible
        count seen so far."""
        from ..runtime.errors import ExecutionHalted

        gen = self._search_gen(feasible, start)
        fulfilled: dict = {}
        try:
            req = next(gen)
            while True:
                if budget is not None:
                    try:
                        budget.check("capacity-probe boundary")
                    except ExecutionHalted as e:
                        e.partial = _search_partial(fulfilled, feasible)
                        raise
                got = self._fulfill(req, on_probe)
                fulfilled.update(got)
                req = gen.send(got)
        except StopIteration as stop:
            return stop.value


def _utilization_impl(static, valid, final):
    import jax.numpy as jnp

    denom_cpu = jnp.sum(jnp.where(valid, static.alloc_mcpu, 0))
    denom_mem = jnp.sum(jnp.where(valid, static.alloc_mem, 0))
    cpu_util = (
        100.0 * jnp.sum(jnp.where(valid, final.used_mcpu, 0)) / jnp.maximum(denom_cpu, 1)
    )
    mem_util = (
        100.0 * jnp.sum(jnp.where(valid, final.used_mem, 0)) / jnp.maximum(denom_mem, 1)
    )
    denom_vg = jnp.sum(jnp.where(valid[:, None], static.vg_cap, 0))
    vg_util = (
        100.0 * jnp.sum(jnp.where(valid[:, None], final.vg_used, 0)) / jnp.maximum(denom_vg, 1)
    )
    return cpu_util, mem_util, vg_util


def _scenario_pinned_impl(static, init, cls, valid, active, pinned, features):
    """TWO chained masked scans with a PER-SCENARIO pin vector — the
    resilience engine's substrate (outage scenario = node mask +
    surviving pods pinned at their committed nodes, displaced pods free
    to reschedule) and the timeline's window step. The passes model
    reality: surviving pods never unbind, so ALL pins commit before any
    displaced pod reschedules — a single interleaved scan would let an
    early displaced pod take capacity a later survivor's unconditional
    pin then overcommits. Pins are force-enabled in the features: the
    original batch may have carried none."""
    import jax.numpy as jnp

    from ..ops import scan as scan_ops

    features = features._replace(pins=True)
    p1, state1 = scan_ops.run_scan_masked(
        static, init, cls, pinned, valid,
        active & (pinned >= 0), features=features,
    )
    p2, final = scan_ops.run_scan_masked(
        static, state1, cls, pinned, valid,
        active & (pinned < 0), features=features,
    )
    placements = jnp.where(pinned >= 0, p1, p2)
    unsched = jnp.sum(placements == -1)
    cpu_util, mem_util, vg_util = _utilization_impl(static, valid, final)
    return placements, unsched, cpu_util, mem_util, vg_util


def _scenario_rows_impl(static, init, cls, valids, actives, pinneds, features):
    import jax

    def one(valid, active, pinned):
        return _scenario_pinned_impl(
            static, init, cls, valid, active, pinned, features
        )

    return jax.vmap(one)(valids, actives, pinneds)


# per-site PROCESS-WIDE jits over the pinned scenario rows (chaos,
# timeline): static/init/masks are traced pytree arguments — not
# closures — so same-shaped batches from DIFFERENT sweep instances
# (each ChaosEngine run, each timeline stepper) hit one compiled
# executable instead of recompiling per instance; per-site wrappers
# keep dispatch/recompile attribution separate (obs/profile.py) —
# "how many window dispatches did this timeline cost" must not hide
# inside the chaos counters.
_SCENARIO_ROWS_JITS: dict = {}


def _scenario_rows_jit(site: str):
    jit = _SCENARIO_ROWS_JITS.get(site)
    if jit is None:
        import jax

        from ..obs import profile

        jit = _SCENARIO_ROWS_JITS[site] = profile.instrument_jit(
            jax.jit(_scenario_rows_impl, static_argnums=(6,)),
            f"{site}_sweep",
            static_argnums=(6,),
            lead_argnum=3,  # valids: the batched scenario-rows axis
        )
    return jit


def _search_partial(fulfilled: dict, feasible) -> dict:
    """Machine-readable progress of an interrupted min-count search:
    completed probes + the best (smallest) feasible count so far."""
    rows = []
    best = None
    for count in sorted(fulfilled):
        res = fulfilled[count]
        ok = bool(feasible(res))
        rows.append(
            {
                "count": int(count),
                "unscheduled": int(res.unscheduled),
                "feasible": ok,
            }
        )
        if ok and (best is None or count < best):
            best = int(count)
    return {
        "phase": "capacity-search",
        "completedProbes": rows,
        "bestCount": best,
    }


def find_min_count_multi(jobs, on_probe=None, budget=None) -> List[Optional[ProbeResult]]:
    """Drive MANY specs' min-count searches in lockstep: `jobs` is a
    list of (CapacitySweep, feasible, start). Each round collects every
    live spec's requested probe counts, dispatches ALL of them deferred
    on the Pallas path, and fetches the stacked outputs in ONE device
    sync — so a what-if sweep over K newnode specs pays the per-sync
    latency once per ROUND (~3-4 rounds total) instead of once per
    probe (~23 for the 8-spec bench). Sweeps on the XLA fallback path
    fulfil their
    requests individually inside the round.

    Replaces the per-guess re-simulation loop of the reference's
    interactive Applier (pkg/apply/apply.go:186-239) across candidate
    node SPECS, not just counts."""
    import jax.numpy as jnp

    from ..ops import pallas_scan
    from ..utils.trace import GLOBAL, phase

    # ship every spec's plan in ONE grouped transfer before round 1
    # (otherwise the first round pays one host->device transfer per
    # plan buffer)
    pallas_scan.preload_plan_group(
        [s._pallas_plan for s, _, _ in jobs if s._pallas_plan is not None]
    )
    gens = []
    pending: List[Optional[List[int]]] = []
    results: List[Optional[ProbeResult]] = []
    for sweep, feasible, start in jobs:
        g = sweep._search_gen(feasible, start, widen=True)
        gens.append(g)
        results.append(None)
        pending.append(next(g))
    live = list(range(len(jobs)))
    rounds = dispatches = syncs = 0
    round_log = []
    while live:
        import time as _time

        if budget is not None:
            budget.check("what-if probe round")
        _t0 = _time.time()
        _n0 = dispatches
        rounds += 1
        answers: List[dict] = [dict() for _ in jobs]
        deferred = []  # (job index, count, valid, device out)
        with phase("sweep/probe-multi"):
            for i in live:
                sweep = jobs[i][0]
                for c in pending[i]:
                    dispatches += 1
                    if sweep._pallas_plan is not None:
                        valid = sweep.node_valid(c)
                        try:
                            out_d = pallas_scan.run_scan_pallas(
                                sweep._pallas_plan,
                                sweep.batch.class_of_pod,
                                sweep.pod_active(valid),
                                valid,
                                pinned=sweep.batch.pinned_node,
                                defer=True,
                            )
                        except (RuntimeError, MemoryError, OSError) as e:
                            from ..runtime.guard import try_downgrade

                            if not try_downgrade(
                                e, label="whatif", frm="pallas", to="xla-scan"
                            ):
                                raise
                            # retire the dead Pallas rung for this
                            # spec; probe() finishes the downgrade
                            sweep._pallas_plan = None
                            answers[i][c] = sweep.probe(c)
                            syncs += 1
                            continue
                        deferred.append((i, c, valid, out_d))
                    else:
                        answers[i][c] = sweep.probe(c)
                        syncs += 1
            # ONE host-blocking point per round and shape: the round's
            # outputs stack on-device and fetch as a single array
            # (every blocking fetch has a fixed cost regardless of
            # size).
            # The stack is padded to a power-of-two row count so the
            # concatenate compiles for O(log max) distinct shapes ever,
            # all hits in the persistent compilation cache after the
            # first encounter.
            by_shape: dict = {}
            for item in deferred:
                by_shape.setdefault(item[3].shape, []).append(item)
            for items in by_shape.values():
                k = len(items)
                bucket = 1 << (k - 1).bit_length()
                rows_d = [it[3] for it in items]
                rows_d += [rows_d[0]] * (bucket - k)
                # the ONE deliberate device->host sync per shape
                # bucket (counted right below): stacking k probe rows
                # and pulling them together is the batching that keeps
                # a K-spec round at one device round-trip
                stacked = np.asarray(jnp.stack(rows_d))  # simonlint: disable=JAX003
                syncs += 1
                for row, (i, c, valid, _) in zip(stacked, items):
                    sweep = jobs[i][0]
                    placements, final = pallas_scan.decode_scan_output(
                        sweep._pallas_plan,
                        row,
                        int(np.asarray(sweep.batch.class_of_pod).shape[0]),
                    )
                    answers[i][c] = sweep._pallas_result(
                        c, valid, placements, final
                    )
        nxt = []
        for i in live:
            if on_probe is not None:
                for r in answers[i].values():
                    on_probe(r)
            try:
                pending[i] = gens[i].send(answers[i])
                nxt.append(i)
            except StopIteration as stop:
                results[i] = stop.value
                pending[i] = None
        live = nxt
        round_log.append(
            f"{dispatches - _n0}p/{_time.time() - _t0:.2f}s"
        )
    GLOBAL.note("whatif-rounds", rounds)
    GLOBAL.note("whatif-dispatches", dispatches)
    GLOBAL.note("whatif-syncs", syncs)
    GLOBAL.note("whatif-round-log", ",".join(round_log))
    return results


def sweep_node_counts(
    cluster: ResourceTypes,
    apps: List[AppResource],
    new_node_spec: Optional[dict],
    counts: List[int],
    mesh=None,
    use_greed: bool = False,
    score_weights=None,
) -> SweepResult:
    """Evaluate `counts` candidate new-node counts in one batched run."""
    max_count = max(counts) if new_node_spec is not None else 0
    sweep = CapacitySweep(
        cluster,
        apps,
        new_node_spec,
        max_count,
        use_greed=use_greed,
        score_weights=score_weights,
    )
    return sweep.probe_many(counts, mesh=mesh)
