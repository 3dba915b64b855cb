"""TPU engine: drives the JAX sequential-commit scan and mirrors its
placements back into the host-side Oracle state.

The Oracle stays the single source of truth for object-level state
(annotations, reports, reason strings); the scan is the compute path.
Every commit the scan makes is replayed on the host through the same
binding code the oracle uses, so oracle state after an engine batch is
identical to having scheduled serially — this is asserted by the
conformance tests (tests/test_engine_conformance.py).

Batch lifecycle (the tiered priority engine's contract): `begin_batch`
encodes a pod batch ONCE — class tensors, features, the XLA scan
static, the port vocabulary; `scan_active(mask)` then dispatches one
scan over any active subset of that batch against the oracle's CURRENT
dynamic state. A priority round that escapes re-dispatches the same
encoding with the committed prefix masked off instead of re-encoding
(and re-compiling: the shapes never change) the shrinking remainder —
an escape-heavy batch pays per round only the dynamic re-encode and
the dispatch, not the full host encode. `schedule(pods)` is the
one-shot form.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..ops.encode import (
    ClusterStatic,
    encode_batch,
    encode_cluster_cached,
    encode_dynamic,
    features_of_batch,
)
from ..runtime.errors import GuardError
from .oracle import Oracle

__all__ = ["PreemptRequest", "SampleRngOverflow", "TpuEngine"]

# per-class summary integers above this magnitude lose int64 headroom
# in the bulk scatter-add; such classes (a >2^55-byte request is ~36 PB
# — malformed input, not a workload) take the per-pod commit path
_BULK_MAX_ABS = 1 << 55


class SampleRngOverflow(GuardError, RuntimeError):
    """A sample-mode Intn draw needed more rejection retries than the
    in-scan bound (ops/scan.py _RNG_KMAX; p < 1e-17 per draw). Raised
    BEFORE any commit is replayed, so the caller (core._schedule_pods)
    can rerun the batch on the serial oracle, whose rejection loop is
    unbounded."""


class PreemptRequest:
    """What a priority round hands scan_active for the device dry run
    (ops/preempt.py): per batch position the effective priority, whether
    a failure there runs the dry run (armed, preemptionPolicy not Never),
    and whether the pod, once committed, is out of scope as a victim;
    `hard_of(ns, pod)` says the same of pods already on a node.
    `extra_slots` counts the batch's pinned pods (table_slots)."""

    def __init__(self, prio, ok, hard, hard_of, extra_slots: int = 0):
        self.prio = np.asarray(prio, np.int64)
        self.ok = np.asarray(ok, bool)
        self.hard = np.asarray(hard, bool)
        self.hard_of = hard_of
        self.extra_slots = extra_slots

    def table(self, oracle, cluster, batch):
        """(ops/preempt.table_np slots, the next commit sequence)."""
        from ..ops import preempt

        k = preempt.table_slots(oracle, cluster, batch, self.extra_slots)
        return preempt.table_np(oracle, k, self.hard_of), oracle._seq_counter + 1

    def inputs(self):
        import jax.numpy as jnp

        from ..ops import preempt

        return preempt.PreemptInput(
            prio=jnp.asarray(self.prio), ok=jnp.asarray(self.ok),
            hard=jnp.asarray(self.hard),
        )


class TpuEngine:
    """Holds the oracle plus a per-node-set cache of the cluster
    encoding: with K apps on an N-node cluster the O(N) ClusterStatic
    build runs once, not K times (per-batch state — DynamicState, pod
    statics, port vocab — is still rebuilt per begin_batch call)."""

    def __init__(self, oracle: Oracle):
        self.oracle = oracle
        self._cluster: ClusterStatic = None
        self._cache_key = None
        # per-batch replay fast path (class ids are batch scoped):
        # classes with no GPU/storage/extender side effects commit via
        # per-class summaries instead of the general bind
        self._last_class_of = None
        self._last_simple = None
        self._class_commit_info = None
        # batch encoding reused across masked rounds (begin_batch)
        self._batch = None
        self._batch_pods: Optional[List[dict]] = None
        self._features = None
        self._scan_static = None
        self._scan_static_cluster = None
        self._bulk_tbl = None
        # sample mode: (pre-round rng history, per-pod consumed-word
        # cumsum) of the last dispatched scan — rewind_sample_rng uses
        # it when a priority-scan escape discards the scanned tail
        self._last_rng = None
        # device mesh override (None = the process-wide configured
        # mesh, parallel/mesh.py current_mesh): the layout planner
        # routes single big-cluster scans through the node-sharded
        # path and scenario batches across the scenario axis
        self.mesh = None
        self._mesh_retired = False
        # (pre_node[P], victims[P, K]) of the last scan_active that ran
        # the device dry run, else None
        self.last_preempt = None

    def cluster_static(self) -> ClusterStatic:
        # keyed on (node count, alloc epoch): GPU-share Reserve mutates
        # ns.alloc[gpu-count], which is baked into ClusterStatic's
        # scalar allocatables — a bind in one batch must invalidate the
        # cache for the next
        key = (len(self.oracle.nodes), self.oracle.alloc_epoch)
        if self._cluster is None or self._cache_key != key:
            self._cluster = encode_cluster_cached(self.oracle)
            self._cache_key = key
        return self._cluster

    def begin_batch(self, pods: List[dict], groups=None) -> None:
        """Encode `pods` once for any number of scan_active dispatches.

        Pods with a spec.nodeName naming an unknown node must be
        filtered out by the caller (the reference leaves them dangling
        in the tracker, simulator.go:221-229). `groups` is the
        (group_of, firsts) content-group index from workload expansion
        (workloads.ExpandIndex) — class keys then resolve once per
        group; without one every pod is its own group
        (ops/encode.py encode_batch)."""
        from ..utils.trace import phase

        oracle = self.oracle
        with phase("engine/encode"):
            with phase("engine/encode-cluster"):
                cluster = self.cluster_static()
            with phase("engine/encode-batch"):
                batch = encode_batch(oracle, cluster, pods, groups=groups)
                from .oracle import ClassCommitCache, simple_commit_mask

                self._batch = batch
                self._batch_pods = pods
                self._last_class_of = np.asarray(batch.class_of_pod)
                self._last_simple = simple_commit_mask(batch, bool(oracle.extenders))
                self._class_commit_info = ClassCommitCache()
                self._bulk_tbl = None
                self._scan_static = None
                sample = getattr(oracle, "select_host", "first-max") == "sample"
                self._features = features_of_batch(
                    cluster, batch,
                    weights=getattr(oracle, "score_weights", None),
                    sample=sample,
                )

    def scan_active(
        self, active: np.ndarray, valid: Optional[np.ndarray] = None,
        preempt=None,
    ) -> np.ndarray:
        """One masked scan over the begin_batch encoding against the
        oracle's CURRENT state. Returns placements for the full batch:
        node index, -1 (active but unschedulable), or -2 (inactive —
        `ops.scan.INACTIVE`, positions masked off by `active`).

        `valid` gates candidate nodes (default: all) — the twin's
        drain-safety and N+K queries evaluate "where do these pods go
        WITHOUT nodes X" as one warm dispatch this way (the scenario
        node mask of the chaos substrate, ops.scan.run_scan_masked
        node_valid). Same shapes, so a masked query re-dispatches the
        compiled scan without recompiling.

        `preempt` (core.py, the priority path) is a PreemptRequest of
        per-batch-position arrays: a pod with `ok` set that fails every
        node runs DefaultPreemption's dry run on the device
        (ops/preempt.py): in the fused kernel where its scope allows,
        else on the XLA scan. Its (pre_node[P], victims[P, K]) land in
        `self.last_preempt`."""
        import jax.numpy as jnp

        from ..ops import pallas_scan
        from ..ops import scan as scan_ops
        from ..ops.encode import to_scan_static, to_scan_state
        from ..utils.trace import GLOBAL, phase

        oracle = self.oracle
        batch = self._batch
        sample = bool(getattr(self._features, "sample", False))
        features = self._features
        self.last_preempt = None
        if preempt is not None:
            features = features._replace(preempt=True)
        with phase("engine/encode"):
            cluster = self.cluster_static()
            node_valid = (
                np.ones(cluster.n, bool)
                if valid is None
                else np.asarray(valid, bool)
            )
            with phase("engine/encode-state"):
                dyn = encode_dynamic(oracle, cluster)
                pre_tab = None if preempt is None else preempt.table(oracle, cluster, batch)
            with phase("engine/kernel-plan"):
                plan = (
                    pallas_scan.build_plan(
                        cluster, batch, dyn, features,
                        weights=features.weights,
                        preempt=None if preempt is None else (
                            *pre_tab, preempt.prio, preempt.ok, preempt.hard
                        ),
                    )
                    if pallas_scan.should_use()
                    else None
                )
            if plan is None:
                with phase("engine/encode-state"):
                    # the scan static survives masked rounds; only a
                    # ClusterStatic rebuild (GPU alloc epoch) invalidates it
                    if self._scan_static is None or self._scan_static_cluster is not cluster:
                        self._scan_static = to_scan_static(cluster, batch)
                        self._scan_static_cluster = cluster
                    init = to_scan_state(dyn, batch)
                    if preempt is not None:
                        from ..ops.preempt import encode_table

                        init = init._replace(preempt=encode_table(*pre_tab))
                if sample:
                    # the scan consumes the oracle's Go RNG stream: hand
                    # its 607-output history in via the carry, and (after
                    # the scan) write the advanced stream back so serial
                    # fallbacks continue the exact sequence
                    hist0 = oracle._rng.history()
                    init = init._replace(
                        rng_hist=jnp.asarray(
                            np.array(hist0, dtype=np.uint64)
                        )
                    )
        # node-axis mesh route: ONE scan over a cluster the layout
        # planner says belongs on the mesh (too big / predicted unfit
        # for one device) — the twin's 100k-node drain/what-if queries
        # ride this (parallel/mesh.py). Classified faults degrade to
        # the single-device path below, trace-noted.
        mesh_route = None
        if plan is None and not sample and preempt is None and not self._mesh_retired:
            from ..parallel import mesh as mesh_mod

            m = self.mesh if self.mesh is not None else mesh_mod.current_mesh()
            if m is not None:
                # site "scan": the single-device masked scan whose
                # compiled records say whether ONE device can hold it
                layout = mesh_mod.plan_layout(
                    "scan", mesh=m, n_scenarios=1, n_nodes=cluster.n,
                    sample=sample,
                )
                if layout.axis == "node":
                    mesh_route = m
        # never a silent fallback: name why the fused kernel was out of
        # scope or unavailable (pallas_scan.fallback_reason)
        GLOBAL.note(
            "batch-kernel",
            pallas_scan.kernel_label(plan)
            if plan is not None
            else (
                "mesh-scan" if mesh_route is not None
                else f"xla-scan ({pallas_scan.fallback_reason()})"
            ),
        )
        if plan is not None:
            # fused single-kernel fast path; bit-identical placements
            # (tests/test_pallas_scan.py)
            from ..obs import profile

            with phase("engine/scan"):
                out_d = pallas_scan.run_scan_pallas(
                    plan,
                    batch.class_of_pod,
                    np.asarray(active, bool),
                    node_valid,
                    pinned=batch.pinned_node,
                    defer=True,
                )
                fetched = np.asarray(out_d)  # blocks on device completion
                profile.record_d2h(fetched.nbytes)
                out, final = pallas_scan.decode_scan_output(
                    plan, fetched, len(batch.class_of_pod)
                )
            if preempt is not None:
                self.last_preempt = (final["pre_node"], final["victims"])
            return np.asarray(out)
        if mesh_route is not None:
            from ..parallel import mesh as mesh_mod

            try:
                with phase("engine/scan"):
                    out, *_stats = mesh_mod.run_node_sharded(
                        mesh_route,
                        self._scan_static,
                        init,
                        batch.class_of_pod,
                        batch.pinned_node,
                        node_valid,
                        np.asarray(active, bool),
                        self._features,
                    )
                return np.asarray(out)
            except (RuntimeError, MemoryError, OSError) as e:
                from ..runtime.guard import try_downgrade

                if not try_downgrade(
                    e, label="engine-scan", frm="mesh-scan", to="xla-scan"
                ):
                    raise
                self._mesh_retired = True
        with phase("engine/scan"):
            placements, final_state = scan_ops.run_scan_masked(
                self._scan_static,
                init,
                jnp.asarray(batch.class_of_pod),
                jnp.asarray(batch.pinned_node),
                jnp.asarray(node_valid),
                jnp.asarray(np.asarray(active, bool)),
                features=features,
                preempt_in=None if preempt is None else preempt.inputs(),
            )
            if sample:
                placements, consumed = placements
            if preempt is not None:
                placements, pre_node, victims = placements
                self.last_preempt = (np.asarray(pre_node), np.asarray(victims))
            out = np.asarray(placements)  # blocks on device completion
            from ..obs import profile

            profile.record_d2h(out.nbytes)
        if sample:
            if bool(np.asarray(final_state.rng_overflow)):
                # oracle state is untouched (commits replay only after
                # this returns); core catches this and reruns serially
                raise SampleRngOverflow(
                    "sample-mode RNG rejection overflow; rerunning the "
                    "batch on the serial oracle"
                )
            self._last_rng = (hist0, np.cumsum(np.asarray(consumed)))
            oracle._rng.set_history(
                [int(x) for x in np.asarray(final_state.rng_hist)]
            )
        return out

    def schedule(self, pods: List[dict]) -> np.ndarray:
        """Returns placements[P]: node index or -1 (unschedulable)."""
        self.begin_batch(pods)
        return self.scan_active(np.ones(len(pods), bool))

    def scan_scenarios(self, actives: np.ndarray) -> np.ndarray:
        """Batch-of-requests entry point (serve/coalescer.py): ONE
        vmapped device dispatch evaluating every row of `actives`
        [Sc, P] as an independent masked scan over the begin_batch
        encoding against the oracle's CURRENT state — Sc what-if
        questions for the price of one dispatch. Scenarios share the
        batch's pin vector and see all nodes; each row's placements
        are identical to scan_active(row) run alone (scenarios never
        see each other's commits — nothing is replayed here).

        Returns placements [Sc, P]: node index, -1 (active but
        unschedulable), or -2 (masked off in that scenario)."""
        import jax.numpy as jnp

        from ..ops.encode import to_scan_static, to_scan_state
        from ..utils.trace import phase

        if bool(getattr(self._features, "sample", False)):
            # the Go-RNG stream is a single serial sequence; scenario
            # rows would race for it (core.py routes sample serially)
            raise ValueError(
                "sample-mode batches cannot ride the scenario scan"
            )
        batch = self._batch
        with phase("engine/encode"):
            cluster = self.cluster_static()
            with phase("engine/encode-state"):
                dyn = encode_dynamic(self.oracle, cluster)
                if self._scan_static is None or self._scan_static_cluster is not cluster:
                    self._scan_static = to_scan_static(cluster, batch)
                    self._scan_static_cluster = cluster
                init = to_scan_state(dyn, batch)
        actives_arr = np.asarray(actives, bool)
        # scenario-axis sharding: coalesced request rows are
        # independent, so a configured mesh splits them across devices
        # ("computation follows sharding" — the jit compiles an SPMD
        # partition per observed input sharding); a classified device
        # fault degrades to the unsharded dispatch, trace-noted
        from ..parallel import mesh as mesh_mod

        m = self.mesh if self.mesh is not None else mesh_mod.current_mesh()
        mesh_route = None
        if m is not None and not self._mesh_retired:
            layout = mesh_mod.plan_layout(
                "scenario_scan", mesh=m,
                n_scenarios=int(actives_arr.shape[0]), n_nodes=cluster.n,
            )
            if layout.axis == "scenario":
                mesh_route = m
        out = None
        if mesh_route is not None:
            try:
                (actives_s,), rows = mesh_mod.shard_scenario_rows(
                    mesh_route, [actives_arr]
                )
                with phase("engine/scan"):
                    out = _scenario_scan_jit()(
                        self._scan_static,
                        init,
                        jnp.asarray(batch.class_of_pod),
                        jnp.asarray(batch.pinned_node),
                        jnp.ones(cluster.n, bool),
                        actives_s,
                        self._features,
                    )
                out = np.asarray(out)[:rows]
            except (RuntimeError, MemoryError, OSError) as e:
                from ..runtime.guard import try_downgrade

                if not try_downgrade(
                    e, label="scenario-scan", frm="mesh-scenario",
                    to="xla-scan",
                ):
                    raise
                self._mesh_retired = True
                out = None
        if out is None:
            with phase("engine/scan"):
                out = _scenario_scan_jit()(
                    self._scan_static,
                    init,
                    jnp.asarray(batch.class_of_pod),
                    jnp.asarray(batch.pinned_node),
                    jnp.ones(cluster.n, bool),
                    jnp.asarray(actives_arr),
                    self._features,
                )
            out = np.asarray(out)
        from ..obs import profile

        profile.record_h2d(actives_arr.nbytes)
        profile.record_d2h(out.nbytes)
        return out

    def rewind_sample_rng(self, batch_pos: int) -> None:
        """Reposition the oracle's sample-mode stream to where it stood
        BEFORE the last scanned round's pod at `batch_pos` consumed its
        draws. A priority-scan escape discards every scanned placement
        from the escape point on and reschedules those pods (serially,
        then by re-dispatching a masked scan), so their draws must be
        un-consumed — the pre-round history advanced by the
        consumed-word prefix is exactly that position
        (gorand.advance_history). Masked-off pods consume zero words,
        so the cumsum is escape-round-local by construction."""
        if self._last_rng is None:
            return
        from ..utils.gorand import advance_history

        hist0, consumed_cum = self._last_rng
        k = int(consumed_cum[batch_pos - 1]) if batch_pos > 0 else 0
        self.oracle._rng.set_history(advance_history(hist0, k))

    def commit_host(self, pod: dict, node_idx: int):
        """Replay one placement into oracle state (same binding code the
        serial path uses, incl. GPU/storage side effects)."""
        self.oracle._reserve_and_bind(pod, self.oracle.nodes[int(node_idx)])

    def commit_host_at(self, pod: dict, node_idx: int, batch_pos: int):
        """commit_host with the pod's position in the last scheduled
        batch: classes with no GPU/storage/extender side effects reduce
        _reserve_and_bind to nodeName+phase+commit, and class members
        share request/port content by class-key construction, so the
        summary/port walk runs once per class (the same fast path the
        capacity replay uses, applier.replay_scenario)."""
        cls_of = self._last_class_of
        if cls_of is not None and batch_pos < len(cls_of):
            cls = int(cls_of[batch_pos])
            if self._last_simple[cls]:
                self._class_commit_info.commit(
                    self.oracle, pod, self.oracle.nodes[int(node_idx)], cls
                )
                return
        self.commit_host(pod, node_idx)

    def bulk_tables(self):
        """(field_tbl[U,7] int64, ports_of_cls, scalars_of_cls,
        bulk_ok[U] bool) for commit_host_bulk — the per-class
        RequestSummary integers resolved once per batch (class members
        share request/port content by class-key construction)."""
        if self._bulk_tbl is None:
            self._bulk_tbl = build_bulk_tables(self._batch, self._last_simple)
        return self._bulk_tbl

    def commit_host_bulk(self, pods, node_idx, cls_ids, prios=None):
        """Bulk replay of a contiguous run of simple-class placements
        (oracle.commit_simple_bulk). Callers gate on `simple &
        bulk_ok`; anything else goes through commit_host_at."""
        field_tbl, ports_of, scalars_of, _ok = self.bulk_tables()
        self.oracle.commit_simple_bulk(
            pods, node_idx, cls_ids, field_tbl, ports_of, scalars_of,
            prios=prios,
        )


def _scan_scenarios_impl(static, init, cls, pinned, valid, actives, features):
    import jax

    from ..ops import scan as scan_ops

    def one(active):
        placements, _final = scan_ops.run_scan_masked(
            static, init, cls, pinned, valid, active, features=features
        )
        return placements

    return jax.vmap(one)(actives)


_SCENARIO_SCAN_JIT = None


def _scenario_scan_jit():
    """The jitted scenario vmap, compiled once per (shape, features)
    pair PROCESS-WIDE: static/init/masks are traced pytree arguments
    (not closures), so a long-lived daemon re-dispatching same-shaped
    request batches hits the jit cache instead of recompiling — the
    warm-compiled-scan property `simon serve` is built on. Wrapped for
    dispatch/recompile accounting (obs/profile.py): the warm-cache
    contract is now a measured number, not a comment."""
    global _SCENARIO_SCAN_JIT
    if _SCENARIO_SCAN_JIT is None:
        import jax

        from ..obs import profile

        _SCENARIO_SCAN_JIT = profile.instrument_jit(
            jax.jit(_scan_scenarios_impl, static_argnums=(6,)),
            "scenario_scan",
            static_argnums=(6,),
            lead_argnum=5,  # actives: the batched request-rows axis
        )
    return _SCENARIO_SCAN_JIT


def build_bulk_tables(batch, simple_mask):
    """Per-class commit tables from a PodBatch's class representatives
    (shared by TpuEngine.commit_host_bulk and the capacity replay,
    applier.replay_masked — the eligibility rule must stay identical in
    both). Only classes marked simple get real rows; the rest never
    reach the bulk path."""
    from ..models import requests as req
    from .oracle import _pod_host_ports

    u = batch.u
    field_tbl = np.zeros((u, 7), dtype=np.int64)
    ports_of = [()] * u
    scalars_of = [()] * u
    bulk_ok = np.zeros(u, dtype=bool)
    for u_i, pod in enumerate(batch.class_pods):
        if not simple_mask[u_i]:
            continue
        s = req.pod_request_summary(pod)
        vals = (s.mcpu, s.mem, s.eph, s.floor_mcpu, s.floor_mem,
                s.nz_mcpu, s.nz_mem)
        if any(abs(v) > _BULK_MAX_ABS for v in vals) or any(
            abs(iv) > _BULK_MAX_ABS for _n, iv in s.scalars
        ):
            continue  # int64 headroom guard: per-pod path
        field_tbl[u_i] = vals
        ports_of[u_i] = tuple(_pod_host_ports(pod))
        scalars_of[u_i] = s.scalars
        bulk_ok[u_i] = True
    return field_tbl, ports_of, scalars_of, bulk_ok
