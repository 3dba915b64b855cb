"""Workload -> pod controller emulation.

Re-implements pkg/utils/utils.go:133-500 (MakeValidPodsBy* /
MakeValidPod / AddWorkloadInfoToPod / SetObjectMetaFromObject) and the
daemonset eligibility path (utils.go:357-398 + the vendored
daemon.Predicates, daemon_controller.go:1251-1258).

Faithful quirks preserved on purpose (they are observable semantics):
- Generated pods take their labels/annotations from the OWNER object,
  not from spec.template.metadata (SetObjectMetaFromObject,
  utils.go:336-347). This is how e.g. GPU annotations on a ReplicaSet
  reach its pods, and what affinity self-matching sees.
- Deployment pods go through an intermediate ReplicaSet whose
  labels/annotations come from the Deployment.
- StatefulSet pod names are `<name>-<ordinal>`; all other generated pods
  are `<owner>-<hash>` (hash width 5 for pods, 10 for workloads).
- PVC volumes are rewritten to hostPath /tmp; env/mounts/probes dropped
  (MakeValidPod, utils.go:410-492).
- StatefulSet volumeClaimTemplates become the `simon/pod-local-storage`
  annotation (utils.go:273-316).
"""

from __future__ import annotations

import copy
import hashlib
import itertools
import json
import marshal
from typing import Optional

import numpy as np

from . import labels as lbl
from .validation import validate_pod_name
from ..utils.quantity import q_value
from ..utils.trace import COUNTERS

# pkg/type/const.go
ANNO_WORKLOAD_KIND = "simon/workload-kind"
ANNO_WORKLOAD_NAME = "simon/workload-name"
ANNO_WORKLOAD_NAMESPACE = "simon/workload-namespace"
ANNO_NODE_LOCAL_STORAGE = "simon/node-local-storage"
ANNO_POD_LOCAL_STORAGE = "simon/pod-local-storage"
ANNO_NODE_GPU_SHARE = "simon/node-gpu-share"
LABEL_NEW_NODE = "simon/new-node"
LABEL_APP_NAME = "simon/app-name"
NEW_NODE_NAME_PREFIX = "simon"
DEFAULT_SCHEDULER_NAME = "default-scheduler"
MAX_NUM_NEW_NODE = 100
WORKLOAD_HASH_DIGITS = 10
POD_HASH_DIGITS = 5

# open-local storage class names (pkg/utils/const.go)
SC_LVM = ("open-local-lvm", "yoda-lvm")
SC_SSD = (
    "open-local-device-ssd",
    "open-local-mountpoint-ssd",
    "yoda-mountpoint-ssd",
    "yoda-device-ssd",
)
SC_HDD = (
    "open-local-device-hdd",
    "open-local-mountpoint-hdd",
    "yoda-mountpoint-hdd",
    "yoda-device-hdd",
)

_name_counter = itertools.count()


def reset_name_counter():
    """Deterministic generated-name suffixes for reproducible tests."""
    global _name_counter
    _name_counter = itertools.count()


def name_counter_state() -> int:
    """The counter's next value, without advancing it (observing
    requires a draw, so the counter is re-seated at the drawn value).
    The serve coalescer snapshots the post-cluster-expansion state once
    and replays it before expanding EVERY request's apps, so a
    coalesced request's generated pod names are identical to the names
    a standalone `simulate()` of that request would mint."""
    global _name_counter
    n = next(_name_counter)
    _name_counter = itertools.count(n)
    return n


def set_name_counter(n: int):
    """Re-seat the generated-name counter at `n` (see
    name_counter_state)."""
    global _name_counter
    _name_counter = itertools.count(n)


def _hash_suffix(digits: int) -> str:
    n = next(_name_counter)
    return hashlib.sha256(str(n).encode()).hexdigest()[:digits]


def _meta_from_owner(owner: dict, kind: str, gen_pod: bool) -> dict:
    """SetObjectMetaFromObject: name = owner-<hash>, labels/annotations
    copied from the owner, ownerReference recorded."""
    ometa = owner.get("metadata") or {}
    name = ometa.get("name", "")
    return {
        "name": f"{name}-{_hash_suffix(POD_HASH_DIGITS if gen_pod else WORKLOAD_HASH_DIGITS)}",
        "namespace": ometa.get("namespace"),
        "generateName": name,
        "annotations": dict(ometa.get("annotations") or {}),
        "labels": dict(ometa.get("labels") or {}),
        "ownerReferences": [
            {
                "kind": kind,
                "name": name,
                "controller": True,
            }
        ],
    }


def _meta_for_replica(base_anno: dict, namespace, gen_name: str, shared_refs) -> dict:
    """Per-replica metadata with the template-invariant parts hoisted
    (annotations still copied per pod — the GPU binder writes a
    per-pod device index into them; labels are assigned by the caller
    from the template's shared dict)."""
    return {
        "name": f"{gen_name}-{_hash_suffix(POD_HASH_DIGITS)}",
        "namespace": namespace,
        "generateName": gen_name,
        "annotations": dict(base_anno),
        "ownerReferences": shared_refs,
    }


def make_valid_pod(pod: dict, _name_only_validation: bool = False) -> dict:
    """MakeValidPod: defaulting + sanitization (utils.go:410-492).

    `_name_only_validation` is the replica fast path: pods expanded
    from one workload template are identical except for the generated
    name, so the caller validates the first clone fully and the rest
    name-only (the reference re-validates every clone; at 100k pods
    that is ~2 s of host time for zero information)."""
    pod = copy.deepcopy(pod)
    meta = pod.setdefault("metadata", {})
    meta.setdefault("labels", {})
    if not meta.get("namespace"):
        meta["namespace"] = "default"
    meta.setdefault("annotations", {})
    spec = pod.setdefault("spec", {})
    if not spec.get("dnsPolicy"):
        spec["dnsPolicy"] = "ClusterFirst"
    if not spec.get("restartPolicy"):
        spec["restartPolicy"] = "Always"
    if not spec.get("schedulerName"):
        spec["schedulerName"] = DEFAULT_SCHEDULER_NAME
    spec.pop("imagePullSecrets", None)
    for key in ("initContainers", "containers"):
        for c in spec.get(key) or []:
            c.pop("volumeMounts", None)
            c.pop("env", None)
            c.pop("livenessProbe", None)
            c.pop("readinessProbe", None)
            c.pop("startupProbe", None)
            sc = c.get("securityContext")
            if sc is not None and "privileged" in sc:
                sc["privileged"] = False
    for v in spec.get("volumes") or []:
        if "persistentVolumeClaim" in v:
            v.pop("persistentVolumeClaim")
            v["hostPath"] = {"path": "/tmp"}
    _validate_pod(pod, _name_only_validation)
    return pod


def _validate_pod(pod: dict, name_only: bool = False):
    """ValidatePod parity (utils.go:519-532): the k8s validation subset
    in models/validation.py, with upstream field-error messages."""
    from .validation import validate_pod, validate_pod_name

    if name_only:
        validate_pod_name(pod)
    else:
        validate_pod(pod)


def add_workload_info(pod: dict, kind: str, name: str, namespace: str) -> dict:
    anno = pod["metadata"].setdefault("annotations", {})
    anno[ANNO_WORKLOAD_KIND] = kind
    anno[ANNO_WORKLOAD_NAME] = name
    anno[ANNO_WORKLOAD_NAMESPACE] = namespace
    return pod


def _expand_template(owner: dict, kind: str, count: int) -> list:
    from .validation import validate_pod_name

    ometa = owner.get("metadata") or {}
    owner_name = ometa.get("name", "")
    owner_ns = ometa.get("namespace", "")
    pods = []
    shared_spec = None
    for i in range(count):
        if shared_spec is None:
            pod = make_valid_pod(
                {
                    "metadata": _meta_from_owner(owner, kind, gen_pod=True),
                    "spec": copy.deepcopy(
                        ((owner.get("spec") or {}).get("template") or {}).get("spec") or {}
                    ),
                }
            )
            shared_spec = pod["spec"]
            first_meta = pod["metadata"]
            # replicas share ONE labels dict and ONE ownerReferences
            # list (content is identical per template; the only
            # post-expansion label write — the app-name label,
            # generate_valid_pods_from_app — stamps the same value for
            # every replica, and nothing mutates ownerReferences).
            # Annotations stay per-pod: the GPU binder writes a per-pod
            # device index there. Sharing lets the encode class-key
            # memo hit by identity (ops/encode.py) instead of
            # re-freezing 100k label dicts.
            shared_labels = first_meta.setdefault("labels", {})
            shared_refs = first_meta.get("ownerReferences")
            namespace = first_meta.get("namespace")
            add_workload_info(pod, kind, owner_name, owner_ns)
            base_anno_full = dict(pod["metadata"]["annotations"])
        else:
            # clone fast path: all replicas share the sanitized
            # template spec — nested structures are read-only after
            # expansion, and direct key writes (the binder's nodeName)
            # land on this clone's own top-level dict. The template was
            # fully validated on the first clone; only the generated
            # name varies. At 100k pods the deepcopy+revalidate path
            # this replaces was ~16 s of host time.
            meta = _meta_for_replica(
                base_anno_full, namespace, owner_name, shared_refs
            )
            meta["labels"] = shared_labels
            pod = {"metadata": meta, "spec": dict(shared_spec)}
            _validate_pod_name_cached(pod)
        pods.append(pod)
    return pods


def pods_from_replica_set(rs: dict) -> list:
    replicas = (rs.get("spec") or {}).get("replicas")
    return _expand_template(rs, "ReplicaSet", 1 if replicas is None else int(replicas))


def pods_from_deployment(deploy: dict) -> list:
    spec = deploy.get("spec") or {}
    # intermediate ReplicaSet named <deploy>-<hash10>, owned by the
    # Deployment (generateReplicaSetFromDeployment, utils.go:185-195);
    # pods then carry an ownerReference to the RS
    rs = {
        "kind": "ReplicaSet",
        "metadata": _meta_from_owner(deploy, "Deployment", gen_pod=False),
        "spec": {
            "selector": spec.get("selector"),
            "replicas": spec.get("replicas"),
            "template": spec.get("template"),
        },
    }
    return pods_from_replica_set(rs)


def pods_from_replication_controller(rc: dict) -> list:
    replicas = (rc.get("spec") or {}).get("replicas")
    return _expand_template(rc, "ReplicationController", 1 if replicas is None else int(replicas))


def pods_from_job(job: dict) -> list:
    completions = (job.get("spec") or {}).get("completions")
    return _expand_template(job, "Job", 1 if completions is None else int(completions))


def pods_from_cron_job(cronjob: dict) -> list:
    spec = cronjob.get("spec") or {}
    job_template = spec.get("jobTemplate") or {}
    meta = _meta_from_owner(cronjob, "CronJob", gen_pod=False)
    anno = dict((job_template.get("metadata") or {}).get("annotations") or {})
    anno["cronjob.kubernetes.io/instantiate"] = "manual"
    meta["annotations"] = anno
    job = {
        "kind": "Job",
        "metadata": meta,
        "spec": (job_template.get("spec") or {}),
    }
    return pods_from_job(job)


def pods_from_stateful_set(sts: dict) -> list:
    spec = sts.get("spec") or {}
    replicas = spec.get("replicas")
    count = 1 if replicas is None else int(replicas)
    name = (sts.get("metadata") or {}).get("name", "")
    pods = _expand_template(sts, "StatefulSet", count)
    for ordinal, pod in enumerate(pods):
        pod["metadata"]["name"] = f"{name}-{ordinal}"
    _set_storage_annotation(pods, spec.get("volumeClaimTemplates") or [])
    return pods


def _set_storage_annotation(pods: list, volume_claim_templates: list):
    """volumeClaimTemplates -> simon/pod-local-storage annotation
    (utils.go:273-316). Size is serialized as a string per the Go
    `json:"size,string"` tag."""
    volumes = []
    for pvc in volume_claim_templates:
        sc = (pvc.get("spec") or {}).get("storageClassName")
        if sc is None:
            continue
        requested = q_value(
            (((pvc.get("spec") or {}).get("resources") or {}).get("requests") or {}).get("storage")
        )
        if sc in SC_LVM:
            kind = "LVM"
        elif sc in SC_SSD:
            kind = "SSD"
        elif sc in SC_HDD:
            kind = "HDD"
        else:
            continue
        volumes.append({"size": str(requested), "kind": kind, "scName": sc})
    if not volumes:
        volumes = []
    payload = json.dumps({"volumes": volumes})
    for pod in pods:
        pod["metadata"].setdefault("annotations", {})[ANNO_POD_LOCAL_STORAGE] = payload


# raw-pod -> intern-key memo: planners and benches expand the SAME
# decoded pod dicts once per simulate() call. Keyed on the raw pod's
# identity — the entry holds a strong ref to the pod, so a key hit
# proves identity (the utils/memo.py contract; decoded inputs are
# read-only after load). The sentinel marks non-JSON-serializable pods
# that must take the full per-pod path every time.
_POD_KEY_CACHE: dict = {}
_POD_KEY_CACHE_MAX = 1 << 17
_UNSERIALIZABLE = object()


def _register_pod_key_cache():
    from ..utils.memo import register_cache

    register_cache(_POD_KEY_CACHE.clear)


_register_pod_key_cache()


def _bound_node_name(pod: dict):
    """A bound pod's spec.nodeName (a non-empty string), else None."""
    spec = pod.get("spec")
    name = spec.get("nodeName") if isinstance(spec, dict) else None
    return name if isinstance(name, str) and name else None


def _pod_intern_key(pod: dict, interned: dict):
    """The pod's group key in `interned` (pod_from_pod's per-batch
    dict): its content as sort-keyed JSON, or _UNSERIALIZABLE."""
    hit = _POD_KEY_CACHE.get(id(pod))
    if hit is not None:
        return hit[1]
    meta = pod.get("metadata") or {}
    rest = {k: v for k, v in pod.items() if k != "metadata"}
    bound = _bound_node_name(pod) is not None
    if bound:
        rest["spec"] = {k: v for k, v in pod["spec"].items() if k != "nodeName"}
    # everything except metadata.name and the VALUE of a bound pod's
    # spec.nodeName participates in the key, so a clone can only differ
    # from its first by name and node (pod_from_pod stamps both per
    # pod) — generateName, apiVersion/kind, status etc. are all shared
    # content. Whether the pod is bound stays in the key: bound and
    # loose pods of one template are two groups.
    content = {
        "metadata": {k: v for k, v in meta.items() if k != "name"},
        "rest": rest,
        "bound": bound,
    }
    # `interned` also maps the content's marshal bytes to its key.
    # marshal format 2 writes no back-references, so the bytes depend
    # on content alone; it is type-exact (1, True and 1.0 give three
    # byte strings) and refuses all but the built-in types, so equal
    # bytes prove equal JSON: the sort-keyed json.dumps runs once per
    # distinct content, not once per pod
    try:
        raw = marshal.dumps(content, 2)
    except ValueError:
        raw = None
    key = interned.get(raw) if raw is not None else None
    if key is None:
        try:
            key = json.dumps(content, sort_keys=True)
        except (TypeError, ValueError):
            key = _UNSERIALIZABLE
        if raw is not None:
            interned[raw] = key
    if len(_POD_KEY_CACHE) >= _POD_KEY_CACHE_MAX:
        _POD_KEY_CACHE.clear()
    _POD_KEY_CACHE[id(pod)] = (pod, key)
    return key


# validated pod NAMES (value-keyed — strings are immutable): re-runs
# over the same decoded inputs re-validate the same 20k-100k generated
# names against the same DNS-1123 regex for zero information. Only
# successes are cached; failures raise before insertion.
_VALID_NAMES: set = set()
_VALID_NAMES_MAX = 1 << 17


def _validate_pod_name_cached(pod: dict) -> None:
    name = (pod.get("metadata") or {}).get("name") or ""
    if name in _VALID_NAMES:
        return
    validate_pod_name(pod)
    if len(_VALID_NAMES) >= _VALID_NAMES_MAX:
        _VALID_NAMES.clear()
    _VALID_NAMES.add(name)


class ExpandIndex:
    """Group index emitted alongside workload expansion: pods of one
    group are clones of one content-identical template — same spec,
    labels, annotations content; everything but metadata.name and the
    node a bound pod names — so queue-sort keys, effective priorities
    and encode class keys resolve ONCE per group and broadcast by
    numpy indexing instead of per-pod Python passes
    (scheduler/queues.py queue_order, ops/encode.py encode_batch).
    Whether a pod is bound (a non-empty spec.nodeName) is group
    content; the node it names is per-pod data, read from the pod
    itself (ops/encode.py group_pins).

    `group_of[i]` is the group of the i-th expanded pod, `firsts[g]`
    a representative pod of group g (one of the expanded pods)."""

    __slots__ = ("group_of", "firsts")

    def __init__(self):
        self.group_of: list = []
        self.firsts: list = []

    def new_group(self, first: dict) -> int:
        self.firsts.append(first)
        return len(self.firsts) - 1

    def mark(self, gid: int) -> None:
        self.group_of.append(gid)

    def mark_group(self, first: dict, count: int) -> None:
        gid = self.new_group(first)
        self.group_of.extend([gid] * count)

    def groups(self) -> tuple:
        """The (group_of, firsts) pair a batch consumer reads
        (ops/encode.py encode_batch, scheduler/core.py)."""
        return np.asarray(self.group_of, dtype=np.int64), self.firsts


def singleton_groups(pods: list) -> tuple:
    """The (group_of, firsts) pair of a batch with no expansion index:
    one group per pod."""
    return np.arange(len(pods), dtype=np.int64), pods


def join_groups(*parts) -> tuple:
    """(group_of, firsts) pairs of consecutive pod runs as one pair."""
    group_of, firsts = [np.zeros(0, dtype=np.int64)], []
    for g, f in parts:
        group_of.append(np.asarray(g, dtype=np.int64) + len(firsts))
        firsts.extend(f)
    return np.concatenate(group_of), firsts


def own_pod(p: dict) -> dict:
    """Shallow-clone a pod's mutation surface (bind writes
    spec.nodeName / status.phase / metadata.annotations): the clone can
    be bound, replayed or re-pinned without touching `p`, whose nested
    content it otherwise shares."""
    q = dict(p)
    q["spec"] = dict(p.get("spec") or {})
    meta = dict(p.get("metadata") or {})
    if meta.get("annotations") is not None:
        meta["annotations"] = dict(meta["annotations"])
    q["metadata"] = meta
    if isinstance(q.get("status"), dict):
        q["status"] = dict(q["status"])
    return q


def pod_from_pod(pod: dict, interned: dict, index: ExpandIndex) -> dict:
    """MakeValidPod for a bare Pod resource, interned: raw pods whose
    content — minus name and a bound pod's nodeName — is identical
    (`interned`, a per-expansion dict) sanitize ONCE and clone like
    workload-template replicas (own_pod of the group's first: shared
    sanitized spec content and labels, per-pod spec.nodeName,
    annotations and status). A 20k-pod app or running cluster built
    from a handful of pod shapes costs a handful of deepcopy+validation
    passes instead of 20k, and the shared spec objects let the encode
    class-key memo hit by identity (ops/encode.py). Validation reads
    no nodeName, so the first's full validation covers its bound
    clones too. Non-JSON-serializable input takes the full per-pod
    path. `index` records the pod's content group."""
    key = _pod_intern_key(pod, interned)
    if key is _UNSERIALIZABLE:
        pod = make_valid_pod(pod)
        index.mark_group(pod, 1)
        return pod
    entry = interned.get(key)
    if entry is None:
        first = make_valid_pod(pod)
        gid = index.new_group(first)
        interned[key] = (first, gid)
        index.mark(gid)
        return first
    first, gid = entry
    clone = own_pod(first)
    clone["metadata"]["name"] = (pod.get("metadata") or {}).get("name", "")
    node_name = _bound_node_name(pod)
    if node_name is not None:
        # a bound group: whether a pod is bound is group content, the
        # node it names is its own
        clone["spec"]["nodeName"] = node_name
        COUNTERS.inc("expand_bound_clones_total")
    meta = clone["metadata"]
    if meta.get("name") or not meta.get("generateName"):
        # name present: format-validate it; name AND generateName both
        # absent: raise the same required error the full path would.
        # generateName-only clones skip: their generateName is part of
        # the intern key, so the first's full validation covered it
        _validate_pod_name_cached(clone)
    index.mark(gid)
    return clone


# ------------------------------------------------------------------ daemonset


def _pin_pod_to_node(pod_spec: dict, node_name: str):
    """SetDaemonSetPodNodeNameByNodeAffinity (utils.go:812-857): inject a
    required matchFields metadata.name term; existing terms get their
    matchFields replaced (matchExpressions kept)."""
    req = {"key": "metadata.name", "operator": "In", "values": [node_name]}
    affinity = pod_spec.setdefault("affinity", {})
    node_aff = affinity.setdefault("nodeAffinity", {})
    required = node_aff.get("requiredDuringSchedulingIgnoredDuringExecution")
    if not required or not required.get("nodeSelectorTerms"):
        node_aff["requiredDuringSchedulingIgnoredDuringExecution"] = {
            "nodeSelectorTerms": [{"matchFields": [req]}]
        }
        return
    for term in required["nodeSelectorTerms"]:
        term["matchFields"] = [req]


def node_should_run_pod(node: dict, pod: dict) -> bool:
    """daemon.Predicates subset used by NodeShouldRunPod
    (utils.go:356-367): nodeName + node affinity + NoSchedule/NoExecute
    taints."""
    if node is None:
        return False
    spec = pod.get("spec") or {}
    node_name = (node.get("metadata") or {}).get("name", "")
    if spec.get("nodeName") and spec["nodeName"] != node_name:
        return False
    if not lbl.pod_matches_node_selector_and_affinity(spec, node):
        return False
    taints = (node.get("spec") or {}).get("taints") or []
    if lbl.find_untolerated_taint(taints, spec.get("tolerations")) is not None:
        return False
    return True


def pods_from_daemon_set(ds: dict, nodes: list) -> list:
    """One pinned pod per eligible node (utils.go:369-398)."""
    meta = ds.get("metadata") or {}
    pods = []
    for n_i, node in enumerate(nodes):
        node_name = (node.get("metadata") or {}).get("name", "")
        pod = {
            "metadata": _meta_from_owner(ds, "DaemonSet", gen_pod=True),
            "spec": copy.deepcopy(((ds.get("spec") or {}).get("template") or {}).get("spec") or {}),
        }
        _pin_pod_to_node(pod["spec"], node_name)
        # name-only is sound here even though clones differ by their
        # matchFields pin: the pin is machine-generated (not user
        # input), and the user template was fully validated on clone 0
        pod = make_valid_pod(pod, _name_only_validation=n_i > 0)
        add_workload_info(pod, "DaemonSet", meta.get("name", ""), meta.get("namespace", ""))
        if node_should_run_pod(node, pod):
            pods.append(pod)
    return pods


# ------------------------------------------------------------------- facade


def pods_excluding_daemon_sets(resources, index: Optional[ExpandIndex] = None) -> list:
    """GetValidPodExcludeDaemonSet (pkg/simulator/utils.go:76-136),
    recording each pod's content group into `index` (ExpandIndex):
    every `_expand_template` call yields one group (replicas are clones
    of one validated template), bare pods group by intern key."""
    index = ExpandIndex() if index is None else index
    interned: dict = {}
    pods = [pod_from_pod(p, interned, index) for p in resources.pods]

    def extend(ps):
        pods.extend(ps)
        if ps:
            index.mark_group(ps[0], len(ps))

    for d in resources.deployments:
        extend(pods_from_deployment(d))
    for rs in resources.replica_sets:
        extend(pods_from_replica_set(rs))
    for rc in resources.replication_controllers:
        extend(pods_from_replication_controller(rc))
    for sts in resources.stateful_sets:
        extend(pods_from_stateful_set(sts))
    for job in resources.jobs:
        extend(pods_from_job(job))
    for cj in resources.cron_jobs:
        extend(pods_from_cron_job(cj))
    return pods


def expand_pods(resources, nodes: list, index: Optional[ExpandIndex] = None) -> list:
    """Every pod a resource set yields, in the reference's order: bare
    pods and workloads (pods_excluding_daemon_sets), then one pod per
    eligible node for each DaemonSet. The one expansion of a running
    cluster (simulate, the plan sweep, serve) and of an app
    (generate_valid_pods_from_app). Daemonset pods pin per node via
    matchFields, so each is its own content group in `index`."""
    index = ExpandIndex() if index is None else index
    pods = pods_excluding_daemon_sets(resources, index)
    for ds in resources.daemon_sets:
        for pod in pods_from_daemon_set(ds, nodes):
            pods.append(pod)
            index.mark_group(pod, 1)
    return pods


def generate_valid_pods_from_app(
    app_name: str, resources, nodes: list, index: Optional[ExpandIndex] = None
) -> list:
    """GenerateValidPodsFromAppResources (pkg/simulator/utils.go:36-73):
    expand_pods, all labelled with the app name. The label stamps once
    per GROUP — clones share their labels dict with the group's first
    by construction, so the write is identical, minus one pass over
    100k pods."""
    index = ExpandIndex() if index is None else index
    g0 = len(index.firsts)
    pods = expand_pods(resources, nodes, index)
    for first in index.firsts[g0:]:
        first["metadata"].setdefault("labels", {})[LABEL_APP_NAME] = app_name
    return pods


def make_valid_node(node: dict, node_name: str) -> dict:
    """MakeValidNodeByNode (utils.go:502-516), incl. its ValidateNode
    call (utils.go:657-671)."""
    from .validation import validate_node

    node = copy.deepcopy(node)
    meta = node.setdefault("metadata", {})
    meta["name"] = node_name
    meta.setdefault("labels", {})["kubernetes.io/hostname"] = node_name
    meta.setdefault("annotations", {})
    validate_node(node)
    return node
