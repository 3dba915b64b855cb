"""One general generator: a configuration file (the cluster deployment)
and a traffic file (the requests made against it) become, from a seed,

- the program's inputs: Kubernetes node, pod and Deployment dicts;
- the reference's inputs: plain integer tables of the same objects.

Both are built here from the same description, so the reference never
reads anything the program made. Shapes are fixed by the files; the
seed chooses content only (which nodes are tainted, the zone of each
node, where the running pods sit, name salts, arrival order).

Copied in spirit from ``bench.build_capacity_scenario`` and
``testing.build_affinity_stress`` (PERF.md lists the originals).
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CLASS_LABEL = "bench/class"
HOSTNAME_KEY = "kubernetes.io/hostname"

_QTY = re.compile(r"^([0-9]+)(m|Ki|Mi|Gi|Ti)?$")
_MULT = {None: 1, "Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40}


def milli_cpu(q: str) -> int:
    m = _QTY.match(str(q))
    if not m or m.group(2) not in (None, "m"):
        raise ValueError(f"unsupported cpu quantity {q!r}")
    return int(m.group(1)) * (1 if m.group(2) == "m" else 1000)


def bytes_of(q: str) -> int:
    m = _QTY.match(str(q))
    if not m or m.group(2) == "m":
        raise ValueError(f"unsupported memory quantity {q!r}")
    return int(m.group(1)) * _MULT[m.group(2)]


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def scaled(n: int, scale: float) -> int:
    """Counts shrink only for a CPU rehearsal (scale < 1)."""
    return n if scale >= 1 or n == 0 else max(1, int(round(n * scale)))


@dataclass
class PodClass:
    """One template in one namespace: every pod of it is identical."""

    name: str
    template: str
    namespace: str
    cpu_m: int
    mem: int
    labels: Dict[str, str]
    tolerates: List[str]
    spread: Optional[dict]
    anti: Optional[dict]


@dataclass
class Tables:
    """The reference's view of a cluster: node columns and class rows."""

    alloc_cpu: np.ndarray
    alloc_mem: np.ndarray
    alloc_pods: np.ndarray
    zone: np.ndarray  # zone id, -1 where the node has no zone label
    taint: np.ndarray  # taint key id, -1 where untainted
    names: List[str]


@dataclass
class Scenario:
    config: dict
    traffic: dict
    seed: int
    scale: float
    classes: List[PodClass]
    class_index: Dict[str, int]
    nodes: List[dict]
    tables: Tables
    taint_keys: List[str]
    bound: List[tuple] = field(default_factory=list)  # (class id, node idx)
    bound_pods: List[dict] = field(default_factory=list)

    # -- program inputs ----------------------------------------------------

    def cluster(self):
        from open_simulator_tpu.models.decode import ResourceTypes

        c = ResourceTypes()
        c.nodes = self.nodes
        c.pods = list(self.bound_pods)
        return c

    def deployment(self, cls: int, replicas: int, name: str) -> dict:
        """The simulator gives expanded pods the Deployment's own labels
        (SetObjectMetaFromObject), so they carry the template's too."""
        pc = self.classes[cls]
        return {
            "kind": "Deployment",
            "metadata": {
                "name": name,
                "namespace": pc.namespace,
                "labels": dict(pc.labels),
            },
            "spec": {
                "replicas": replicas,
                "template": {
                    "metadata": {"labels": dict(pc.labels)},
                    "spec": _pod_spec(pc, self.taint_keys),
                },
            },
        }

    def new_node(self) -> dict:
        nn = self.config["new_node"]
        return _node_dict("template", nn, {}, None, None)

    # -- reference inputs --------------------------------------------------

    def with_new_nodes(self, count: int) -> Tables:
        """Node tables of the base cluster plus `count` template nodes,
        appended in order (the planner's candidate nodes)."""
        nn = self.config["new_node"]
        t = self.tables
        k = np.arange(count)
        return Tables(
            alloc_cpu=np.concatenate([t.alloc_cpu, np.full(count, milli_cpu(nn["cpu"]))]),
            alloc_mem=np.concatenate([t.alloc_mem, np.full(count, bytes_of(nn["memory"]))]),
            alloc_pods=np.concatenate([t.alloc_pods, np.full(count, int(nn["pods"]))]),
            zone=np.concatenate([t.zone, np.full(count, -1)]),
            taint=np.concatenate([t.taint, np.full(count, -1)]),
            names=t.names + [f"new-{i}" for i in k],
        )

    def ordered(self, entries: List[tuple]) -> List[int]:
        """Class of every pod of one app, in the order the scheduler
        takes them: Deployments in list order, replicas in order, then
        the queue sorts of the simulator (pods with tolerations first,
        then pods with a nodeSelector; both stable)."""
        seq = []
        for cls, replicas in entries:
            seq.extend([cls] * replicas)
        # no template carries a nodeSelector, so that sort is the identity
        return sorted(seq, key=lambda c: not self.classes[c].tolerates)


def _pod_spec(pc: PodClass, taint_keys: List[str]) -> dict:
    spec = {
        "containers": [
            {
                "name": "pause",
                "image": "registry.k8s.io/pause:3.9",
                "ports": [{"containerPort": 80}],
                "resources": {
                    "requests": {"cpu": f"{pc.cpu_m}m", "memory": str(pc.mem)},
                    "limits": {"cpu": f"{pc.cpu_m}m", "memory": str(pc.mem)},
                },
            }
        ]
    }
    if pc.tolerates:
        spec["tolerations"] = [
            {"key": k, "operator": "Exists"} for k in pc.tolerates
        ]
    aff = {}
    if pc.anti:
        aff["podAntiAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {
                    "labelSelector": {"matchLabels": dict(pc.anti["selector"])},
                    "topologyKey": pc.anti["topologyKey"],
                    "namespaces": list(pc.anti["namespaces"]),
                }
            ]
        }
    if aff:
        spec["affinity"] = aff
    if pc.spread:
        spec["topologySpreadConstraints"] = [
            {
                "maxSkew": int(pc.spread["maxSkew"]),
                "topologyKey": pc.spread["topologyKey"],
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": dict(pc.spread["selector"])},
            }
        ]
    return spec


def _node_dict(name, shape, labels, taint, zone_key_val) -> dict:
    lab = {HOSTNAME_KEY: name, **labels}
    if zone_key_val is not None:
        lab[zone_key_val[0]] = zone_key_val[1]
    node = {
        "kind": "Node",
        "metadata": {"name": name, "labels": lab},
        "status": {
            "allocatable": {
                "cpu": str(shape["cpu"]),
                "memory": str(shape["memory"]),
                "pods": str(shape["pods"]),
            },
            "capacity": {
                "cpu": str(shape["cpu"]),
                "memory": str(shape["memory"]),
                "pods": str(shape["pods"]),
            },
        },
    }
    if taint is not None:
        node["spec"] = {"taints": [dict(taint)]}
    return node


def _classes(config: dict, traffic: dict) -> List[PodClass]:
    """Every (template, namespace) pair the config and traffic use, in
    a fixed order: the config's templates, then traffic overrides."""
    tmpl = config["templates"]
    wanted = [(t, tmpl[t].get("namespace", "default")) for t in tmpl]
    for section in ("init", "workload"):
        for e in traffic.get(section, []):
            key = (e["template"], e.get("namespace", tmpl[e["template"]].get("namespace", "default")))
            if key not in wanted:
                wanted.append(key)
    out = []
    for t, ns in wanted:
        d = tmpl[t]
        name = t if ns == d.get("namespace", "default") else f"{t}.{ns}"
        out.append(
            PodClass(
                name=name,
                template=t,
                namespace=ns,
                cpu_m=milli_cpu(d["cpu"]),
                mem=bytes_of(d["memory"]),
                labels={CLASS_LABEL: name, **d.get("labels", {})},
                tolerates=list(d.get("tolerates", [])),
                spread=d.get("spread"),
                anti=d.get("anti"),
            )
        )
    return out


def class_of(scn_classes, template: str, namespace: Optional[str], config) -> int:
    ns = namespace or config["templates"][template].get("namespace", "default")
    for i, pc in enumerate(scn_classes):
        if pc.template == template and pc.namespace == ns:
            return i
    raise KeyError((template, namespace))


def build(config_name: str, traffic_name: str, seed: int, scale: float = 1.0) -> Scenario:
    config = load_json("configs", config_name)
    traffic = load_json("traffic", traffic_name)
    rng = np.random.default_rng(seed)
    salt = f"{seed % 1000003:06d}"
    nc = config["nodes"]
    n = scaled(int(nc["count"]), scale)
    zones = int(nc.get("zones", 0))
    # zones: equal sizes, seeded assignment
    zone = (
        rng.permutation(np.arange(n) % zones) if zones else np.full(n, -1)
    )
    taint_cfg = nc.get("taint")
    taint_keys = [taint_cfg["key"]] if taint_cfg else []
    taint = np.full(n, -1)
    if taint_cfg:
        k = -(-n // int(taint_cfg["every"]))  # one node in `every`
        taint[rng.choice(n, size=k, replace=False)] = 0
    names = [f"node-{salt}-{i:05d}" for i in range(n)]
    nodes = []
    for i in range(n):
        zkv = (nc["zone_key"], f"zone-{int(zone[i])}") if zones else None
        t = (
            {"key": taint_cfg["key"], "value": taint_cfg["value"], "effect": taint_cfg["effect"]}
            if taint[i] >= 0
            else None
        )
        nodes.append(_node_dict(names[i], nc, {}, t, zkv))
    tables = Tables(
        alloc_cpu=np.full(n, milli_cpu(nc["cpu"]), dtype=np.int64),
        alloc_mem=np.full(n, bytes_of(nc["memory"]), dtype=np.int64),
        alloc_pods=np.full(n, int(nc["pods"]), dtype=np.int64),
        zone=zone.astype(np.int64),
        taint=taint.astype(np.int64),
        names=names,
    )
    classes = _classes(config, traffic)
    scn = Scenario(
        config=config,
        traffic=traffic,
        seed=seed,
        scale=scale,
        classes=classes,
        class_index={pc.name: i for i, pc in enumerate(classes)},
        nodes=nodes,
        tables=tables,
        taint_keys=taint_keys,
    )
    _place_running(scn, rng, salt)
    return scn


def _place_running(scn: Scenario, rng, salt: str) -> None:
    """Running pods (traffic `init`), bound to nodes chosen
    from the seed: each entry's pods go round a seeded permutation of
    the nodes its class may use, so a class never doubles up on a node
    before every eligible node has one. Every placement must fit."""
    t = scn.tables
    n = len(t.names)
    used_cpu = np.zeros(n, np.int64)
    used_mem = np.zeros(n, np.int64)
    used_pods = np.zeros(n, np.int64)
    k = 0
    for e in scn.traffic.get("init", []):
        cls = class_of(scn.classes, e["template"], e.get("namespace"), scn.config)
        pc = scn.classes[cls]
        count = scaled(int(e["count"]), scn.scale)
        ok = np.ones(n, bool)
        if not pc.tolerates:
            ok &= t.taint < 0
        eligible = np.flatnonzero(ok)
        perm = rng.permutation(eligible)
        if pc.anti and count > len(perm):
            raise ValueError(f"{count} {pc.name} pods exclude each other on {len(perm)} nodes")
        for j in range(count):
            i = int(perm[j % len(perm)])
            used_cpu[i] += pc.cpu_m
            used_mem[i] += pc.mem
            used_pods[i] += 1
            scn.bound.append((cls, i))
            spec = _pod_spec(pc, scn.taint_keys)
            spec["nodeName"] = t.names[i]
            scn.bound_pods.append(
                {
                    "kind": "Pod",
                    "metadata": {
                        "name": f"{pc.name.replace('.', '-')}-{salt}-{k:06d}",
                        "namespace": pc.namespace,
                        "labels": dict(pc.labels),
                    },
                    "spec": spec,
                    "status": {"phase": "Running"},
                }
            )
            k += 1
    if (used_cpu > t.alloc_cpu).any() or (used_mem > t.alloc_mem).any() or (
        used_pods > t.alloc_pods
    ).any():
        raise ValueError("running pods do not fit their nodes")
