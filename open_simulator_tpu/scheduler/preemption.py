"""Priority & preemption (DefaultPreemption PostFilter + PrioritySort).

Reimplements the kube-scheduler v1.20.5 preemption cycle
(vendor/.../framework/plugins/defaultpreemption/default_preemption.go):

- pod priority (component-helpers/scheduling/corev1/helpers.go:25) plus
  an admission-emulation extension: the fake apiserver of the reference
  has no admission chain, so `priorityClassName` on a pod resolves here
  against decoded PriorityClass objects and the two builtin classes —
  exactly what the real priority admission plugin would stamp into
  `spec.priority`.
- PodEligibleToPreemptOthers (default_preemption.go:231-255): a
  `preemptionPolicy: Never` pod never preempts. The terminating-pods
  check is vacuous (no graceful deletion in the simulator).
- nodesWherePreemptionMightHelp (default_preemption.go:259-271): nodes
  rejected with UnschedulableAndUnresolvable (node selector/affinity,
  taints, nodeName, unschedulable node, missing topology key, required
  pod-affinity rules — see oracle.Code) are excluded.
- selectVictimsOnNode (default_preemption.go:578-673): remove all
  lower-priority pods; if the preemptor then fits, reprieve as many as
  possible — PDB-violating victims first, then non-violating, both in
  MoreImportantPod order (priority desc, earlier start first; start
  time is the oracle's commit sequence — simulated pods carry no
  status.startTime).
- filterPodsWithPDBViolation (default_preemption.go:736-781): budget =
  `status.disruptionsAllowed` (defaults to 0, matching the reference
  under a fake client where no disruption controller ever fills the
  status in).
- pickOneNodeForPreemption (default_preemption.go:443-561): the 6
  tie-break criteria, with the final "sort of randomly" step pinned to
  first-in-node-order (same documented determinism deviation as
  selectHost, scheduler/oracle.py).

Deviations (documented, deliberate):
- Candidate search is exhaustive and deterministic: the reference
  dry-runs a random-offset sample of ~10% of nodes
  (default_preemption.go:169-184, getOffsetAndNumCandidates) and its
  parallel candidate list is unordered; we evaluate every potential
  node. More candidates never yields a worse pick.
- The dry run reverses GPU-share device and open-local VG/device state
  too. The reference's dry-run NodeInfo clone only adjusts resource
  accounting, so its gpu/local-storage plugin caches go stale during
  preemption — a bug we do not reproduce.
- Victims are actually removable here: the Simulator re-enqueues them
  (their controller would recreate them in a real cluster), whereas
  the reference deletes them from the fake cluster and the preemptor
  is still reported failed by the serial handshake. See
  scheduler/core.py.

The tpu engine runs this same cycle inside its scan where victims can
affect a preemptor only through NodeResourcesFit and the pod count
(ops/preempt.py); elsewhere it escapes to the functions below.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..models import labels as lbl

# Builtin PriorityClasses (pkg/apis/scheduling/types.go upstream).
BUILTIN_PRIORITY_CLASSES = {
    "system-cluster-critical": 2000000000,
    "system-node-critical": 2000001000,
}


@dataclass
class PriorityAdmission:
    """Admission emulation for the priority plugin: what the real
    apiserver's Priority admission controller would stamp into
    spec.priority / spec.preemptionPolicy from PriorityClass objects.
    Honors value, globalDefault, and per-class preemptionPolicy."""

    values: Dict[str, int] = field(default_factory=dict)
    policies: Dict[str, str] = field(default_factory=dict)
    global_default: int = 0

    def priority(self, pod: dict) -> int:
        """PodPriority (corev1/helpers.go:25) with admission defaults."""
        spec = pod.get("spec") or {}
        if spec.get("priority") is not None:
            return int(spec["priority"])
        name = spec.get("priorityClassName")
        if name and name in self.values:
            return self.values[name]
        return self.global_default

    def preemption_policy(self, pod: dict) -> str:
        spec = pod.get("spec") or {}
        if spec.get("preemptionPolicy") is not None:
            return str(spec["preemptionPolicy"])
        name = spec.get("priorityClassName")
        if name and name in self.policies:
            return self.policies[name]
        return "PreemptLowerPriority"


def build_priority_resolver(priority_classes: List[dict]) -> PriorityAdmission:
    """PriorityAdmission from decoded PriorityClass objects plus the
    builtins (builtin names are rejected by the real apiserver, so user
    classes never shadow them)."""
    adm = PriorityAdmission(values=dict(BUILTIN_PRIORITY_CLASSES))
    for pc in priority_classes or []:
        name = (pc.get("metadata") or {}).get("name")
        if not name:
            continue
        adm.values[name] = int(pc.get("value", 0))
        if pc.get("preemptionPolicy"):
            adm.policies[name] = str(pc["preemptionPolicy"])
        if pc.get("globalDefault"):
            adm.global_default = int(pc.get("value", 0))
    return adm


def pod_priority(pod: dict, resolver: Optional[PriorityAdmission] = None) -> int:
    if resolver is None:
        resolver = PriorityAdmission(values=dict(BUILTIN_PRIORITY_CLASSES))
    return resolver.priority(pod)


def pod_uses_priority(pod: dict, resolver: Optional[PriorityAdmission] = None) -> bool:
    """True when the pod's *effective* priority is non-zero — a batch
    containing such pods rides the ordered scan optimistically with a
    per-pod serial escape hatch for failures that pass the PostFilter
    preemption gates (core.py._schedule_pods_priority).

    An explicit `spec.priority: 0` (what a real apiserver stamps on
    every default pod, so every live-cluster import carries it) is NOT
    a signal: a uniform-priority-0 workload can neither preempt nor be
    reordered, and must keep the TPU fast path."""
    return pod_priority(pod, resolver) != 0


def batch_priorities(pods: List[dict], resolver: Optional[PriorityAdmission] = None):
    """Effective priorities of a whole batch as one int64 vector — the
    single per-pod resolution pass of the tiered scan engine. The
    PrioritySort key, the engine-routing check (`any non-zero?`), the
    tier partition, and the bulk-commit `_min_prio` update all read
    this array instead of re-calling `oracle.pod_priority` per pod
    (which used to run 3x per pod per batch on the dense-priority
    path)."""
    import numpy as np

    if resolver is None:
        resolver = PriorityAdmission(values=dict(BUILTIN_PRIORITY_CLASSES))
    prio = resolver.priority
    return np.fromiter((prio(p) for p in pods), dtype=np.int64, count=len(pods))


def tier_escape_mask(prios, min_prio, preempt_enabled: bool):
    """Per-pod "armed" mask for the tiered scan: True where a FAILING
    pod would pass the serial PostFilter priority gate and must escape
    to the serial preemption cycle (the per-pod preemptionPolicy gate
    is applied lazily by the caller, on failing pods only).

    `prios` is the remaining PrioritySorted suffix; `min_prio` the
    oracle's pre-round `_min_prio`. The batch partitions into
    contiguous equal-priority TIERS, and within a tier the predicate is
    a constant: the serial gate for pod i is
    `prio[i] > min(min_prio, prefix_min(prios[:i]))`, and since
    `x > min(y, x)` is `x > y`, every pod of a tier reduces to
    `tier_prio > min(min_prio, prefix_min_before_tier)`. The whole
    check is three numpy passes over tier boundaries instead of a
    Python predicate per pod.

    Returns (armed[P] bool, n_tiers)."""
    import numpy as np

    p = len(prios)
    if p == 0:
        return np.zeros(0, dtype=bool), 0
    boundaries = np.flatnonzero(np.diff(prios)) + 1
    tier_start = np.concatenate([[0], boundaries])
    tier_len = np.diff(np.concatenate([tier_start, [p]]))
    n_tiers = len(tier_start)
    if not preempt_enabled:
        return np.zeros(p, dtype=bool), n_tiers
    tier_prio = prios[tier_start]
    hi = np.iinfo(np.int64).max
    floor = int(min_prio) if min_prio < hi else hi  # _min_prio starts math.inf
    pm_before = np.concatenate(
        [[hi], np.minimum.accumulate(tier_prio)[:-1]]
    )
    armed_tier = tier_prio > np.minimum(pm_before, floor)
    return np.repeat(armed_tier, tier_len), n_tiers


@dataclass
class Candidate:
    """One preemption candidate node (default_preemption.go Candidate):
    victims ordered by MoreImportantPod (priority desc)."""

    node_index: int
    node_name: str
    victims: List[dict]
    num_pdb_violations: int


@dataclass
class PreemptionResult:
    node_name: str
    node_index: int
    victims: List[dict] = field(default_factory=list)


def _pdb_selects(pdb: dict, pod_ns: str, pod_labels: dict) -> bool:
    """Whether a PodDisruptionBudget's selector matches a pod of
    namespace `pod_ns` with (non-empty) `pod_labels`."""
    if (((pdb.get("metadata") or {}).get("namespace")) or "default") != pod_ns:
        return False
    selector = (pdb.get("spec") or {}).get("selector")
    # nil/empty selector matches nothing (the metav1
    # LabelSelectorAsSelector empty-selector rule there)
    if not selector or not (
        selector.get("matchLabels") or selector.get("matchExpressions")
    ):
        return False
    return lbl.match_labels_selector(selector, pod_labels)


def filter_pods_with_pdb_violation(
    pods: List[dict], pdbs: List[dict]
) -> Tuple[List[dict], List[dict]]:
    """filterPodsWithPDBViolation (default_preemption.go:736-781).
    Stable: preserves the order of `pods` within each group."""
    allowed = [
        int(((pdb.get("status") or {}).get("disruptionsAllowed")) or 0) for pdb in pdbs
    ]
    violating, non_violating = [], []
    for pod in pods:
        meta = pod.get("metadata") or {}
        pod_labels = meta.get("labels") or {}
        pod_ns = meta.get("namespace") or "default"
        violated = False
        if pod_labels:
            for i, pdb in enumerate(pdbs):
                if not _pdb_selects(pdb, pod_ns, pod_labels):
                    continue
                disrupted = ((pdb.get("status") or {}).get("disruptedPods")) or {}
                if meta.get("name") in disrupted:
                    continue
                allowed[i] -= 1
                if allowed[i] < 0:
                    violated = True
        (violating if violated else non_violating).append(pod)
    return violating, non_violating


def pdb_matched(pod: dict, pdbs: List[dict]) -> bool:
    """Whether a PodDisruptionBudget selects the pod (same namespace,
    non-empty selector): the pods filterPodsWithPDBViolation may count
    as violating. Ignores disruptedPods and the budget, so it may say
    True of a pod the serial walk would find non-violating — the
    device dry run treats such a pod as out of scope (ops/preempt.py),
    which only costs a serial escape."""
    if not pdbs:
        return False
    meta = pod.get("metadata") or {}
    pod_labels = meta.get("labels") or {}
    if not pod_labels:
        return False
    pod_ns = meta.get("namespace") or "default"
    return any(_pdb_selects(pdb, pod_ns, pod_labels) for pdb in pdbs)


def victim_out_of_scope(oracle, ns, pod: dict) -> bool:
    """A committed pod the device dry run may not evict: matched by a
    PodDisruptionBudget, or holding GPU share or open-local volumes."""
    from ..models import storage as stor

    return (
        pdb_matched(pod, oracle.pdbs)
        or stor.pod_gpu_memory(pod) > 0
        or oracle._pod_key(pod) in ns.local_allocs
    )


def pick_one_node(candidates: List[Candidate], oracle) -> Optional[Candidate]:
    """pickOneNodeForPreemption (default_preemption.go:443-561)."""
    if not candidates:
        return None
    if len(candidates) == 1:
        return candidates[0]

    def start_seq(pod: dict) -> int:
        return oracle.commit_seq_of(pod)

    # 1. minimum PDB violations
    best = min(c.num_pdb_violations for c in candidates)
    pool = [c for c in candidates if c.num_pdb_violations == best]
    if len(pool) == 1:
        return pool[0]
    # 2. minimum highest-priority victim (victims sorted desc by priority)
    best = min(oracle.pod_priority(c.victims[0]) for c in pool)
    pool = [c for c in pool if oracle.pod_priority(c.victims[0]) == best]
    if len(pool) == 1:
        return pool[0]
    # 3. minimum sum of victim priorities
    best = min(sum(oracle.pod_priority(p) for p in c.victims) for c in pool)
    pool = [
        c for c in pool if sum(oracle.pod_priority(p) for p in c.victims) == best
    ]
    if len(pool) == 1:
        return pool[0]
    # 4. minimum number of victims
    best = min(len(c.victims) for c in pool)
    pool = [c for c in pool if len(c.victims) == best]
    if len(pool) == 1:
        return pool[0]
    # 5. latest earliest-start-time among each node's *highest-priority*
    #    victims (GetEarliestPodStartTime considers only pods at the max
    #    priority on the node; proxy: commit seq — higher = started later)
    def earliest_high_prio_start(c: Candidate) -> int:
        top = max(oracle.pod_priority(p) for p in c.victims)
        return min(start_seq(p) for p in c.victims if oracle.pod_priority(p) == top)

    best = max(earliest_high_prio_start(c) for c in pool)
    pool = [c for c in pool if earliest_high_prio_start(c) == best]
    # 6. first in node order (reference: "sort of randomly")
    return min(pool, key=lambda c: c.node_index)


def select_victims_on_node(oracle, pod: dict, ns, pdbs: List[dict], ctx=None):
    """selectVictimsOnNode (default_preemption.go:578-673) against live
    oracle state: victims are removed, reprieves re-added, and on exit
    the node is restored exactly (undo tokens carry the GPU device ids
    and open-local allocations of each removed pod).

    Returns (victims, num_pdb_violations) or None when preemption on
    this node cannot help.
    """
    preemptor_prio = oracle.pod_priority(pod)
    potential = [p for p in ns.pods if oracle.pod_priority(p) < preemptor_prio]
    if not potential:
        return None
    undo = {}
    removed: List[dict] = []
    # a reprieve re-inserts at a position recorded while other pods were
    # out, so the restores alone can permute the node's pods; upstream
    # dry-runs on a clone, and the node keeps its order
    order = list(ns.pods)

    def key(p):
        m = p.get("metadata") or {}
        return (m.get("namespace") or "default", m.get("name", ""))

    def remove(p):
        undo[key(p)] = oracle.remove_pod_from_node(ns, p)
        removed.append(p)

    def restore_all():
        for p in reversed(removed):
            oracle.restore_pod_to_node(ns, p, undo[key(p)])
        ns.pods[:] = order

    for p in list(potential):
        remove(p)
    try:
        if not oracle.passes_filters_on_node(pod, ns, ctx=ctx):
            return None
        # MoreImportantPod order: priority desc, earlier start first
        potential.sort(
            key=lambda p: (-oracle.pod_priority(p), oracle.commit_seq_of(p))
        )
        violating, non_violating = filter_pods_with_pdb_violation(potential, pdbs)
        victims: List[dict] = []
        num_violating = 0

        def reprieve(p) -> bool:
            oracle.restore_pod_to_node(ns, p, undo[key(p)])
            removed.remove(p)
            if oracle.passes_filters_on_node(pod, ns, ctx=ctx):
                return True
            undo[key(p)] = oracle.remove_pod_from_node(ns, p)
            removed.append(p)
            victims.append(p)
            return False

        for p in violating:
            if not reprieve(p):
                num_violating += 1
        for p in non_violating:
            reprieve(p)
        return victims, num_violating
    finally:
        restore_all()


def run_preemption(oracle, pod: dict, codes: Dict[int, str]) -> Optional[PreemptionResult]:
    """The preempt() pipeline (default_preemption.go:118-163) including
    extender ProcessPreemption (CallExtenders,
    default_preemption.go:146): preemption-capable extenders see the
    dry-run candidate map and may drop nodes or rewrite victim lists
    before pickOneNodeForPreemption. A non-ignorable extender error
    raises ExtenderError — the caller fails this preemption attempt
    (PostFilter error status), not the run.

    `codes` is the per-node-index failure code map from the failed
    scheduling cycle ("unschedulable" | "unresolvable")."""
    # PodEligibleToPreemptOthers — policy comes from spec.preemptionPolicy
    # or, absent that, the pod's PriorityClass (admission emulation)
    if oracle.pod_preemption_policy(pod) == "Never":
        return None
    pdbs = oracle.pdbs
    # the pod-level filter context is cluster-state independent; compute
    # it once for the whole dry run instead of per passes_filters call
    ctx = oracle._pod_filter_ctx(pod)
    candidates: List[Candidate] = []
    for ns in oracle.nodes:
        # nodesWherePreemptionMightHelp: filters marked the node
        # UnschedulableAndUnresolvable -> removing pods cannot help
        if codes.get(ns.index) == "unresolvable":
            continue
        got = select_victims_on_node(oracle, pod, ns, pdbs, ctx=ctx)
        if got is None:
            continue
        victims, num_violating = got
        # every victim reprieved -> the cycle's failure on this node
        # came from state the dry run does not model (an extender
        # filter); evicting nothing cannot help, and the vendored
        # pickOneNodeForPreemption would index victims[0] (a latent
        # upstream panic, default_preemption.go:475). Drop it.
        if not victims:
            continue
        candidates.append(
            Candidate(
                node_index=ns.index,
                node_name=ns.name,
                victims=victims,
                num_pdb_violations=num_violating,
            )
        )
    candidates = _call_preemption_extenders(oracle, pod, candidates)
    best = pick_one_node(candidates, oracle)
    if best is None:
        return None
    return PreemptionResult(
        node_name=best.node_name, node_index=best.node_index, victims=best.victims
    )


def _call_preemption_extenders(
    oracle, pod: dict, candidates: List[Candidate]
) -> List[Candidate]:
    """CallExtenders adaptation over oracle Candidates. Rebuilt
    candidates keep the extender's victim lists; like the reference's
    convertToNodeNameToVictims they carry 0 PDB violations. A node whose
    victim list the extender emptied is dropped — deliberate deviation:
    the vendored v1.20.5 pickOneNodeForPreemption would panic on it
    (victims.Pods[0], default_preemption.go:476; later k8s releases
    return such a node immediately as the nominee), and with no eviction
    the retry cycle cannot succeed here anyway. Raises ExtenderError on
    a non-ignorable extender failure."""
    extenders = getattr(oracle, "extenders", None) or []
    if not candidates or not any(e.supports_preemption for e in extenders):
        return candidates
    from .extender import call_extenders_preemption

    victims_map = {
        c.node_name: {
            "pods": list(c.victims),
            "numPDBViolations": c.num_pdb_violations,
        }
        for c in candidates
    }
    new_map = call_extenders_preemption(
        extenders,
        pod,
        victims_map,
        lambda name: oracle.nodes[oracle.node_index[name]].pods,
    )
    if new_map is victims_map:
        return candidates
    out: List[Candidate] = []
    for c in candidates:
        v = new_map.get(c.node_name)
        if v is None or not v.get("pods"):
            continue
        # restore the MoreImportantPod invariant pick_one_node relies on
        # (victims[0] = highest-priority victim) — the extender's
        # response order is arbitrary
        victims = sorted(
            v["pods"],
            key=lambda p: (-oracle.pod_priority(p), oracle.commit_seq_of(p)),
        )
        out.append(
            Candidate(
                node_index=c.node_index,
                node_name=c.node_name,
                victims=victims,
                num_pdb_violations=int(v.get("numPDBViolations") or 0),
            )
        )
    return out
