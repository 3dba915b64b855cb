"""Pod classes come from pod content alone: a spec.nodeName pin is
per-pod data (PodBatch.pinned_node), never class content, so the bound
pods of one template on N nodes encode as ONE class instead of N
(ops/encode.py:_class_key). A running cluster then encodes in
O(templates x nodes), and placements stay the serial oracle's."""

import copy

import numpy as np
import pytest

from open_simulator_tpu.models import workloads as wl
from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.ops import pallas_scan
from open_simulator_tpu.ops.encode import (
    encode_batch,
    encode_cluster,
    encode_dynamic,
    features_of_batch,
)
from open_simulator_tpu.scheduler.core import AppResource, simulate
from open_simulator_tpu.scheduler.oracle import Oracle
from open_simulator_tpu.utils.trace import COUNTERS

ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"


def _node(i, zones=3):
    name = f"n{i:04d}"
    return {
        "kind": "Node",
        "metadata": {
            "name": name,
            "labels": {HOST_KEY: name, ZONE_KEY: f"z{i % zones}"},
        },
        "status": {"allocatable": {"cpu": "4", "memory": "32Gi", "pods": "110"}},
    }


def _spec(app, cpu="100m", spread=False, anti=False):
    spec = {
        "containers": [
            {
                "name": "c",
                "image": "pause",
                "resources": {"requests": {"cpu": cpu, "memory": "500Mi"}},
            }
        ]
    }
    if spread:
        spec["topologySpreadConstraints"] = [
            {
                "maxSkew": 1,
                "topologyKey": ZONE_KEY,
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"app": app}},
            }
        ]
    if anti:
        spec["affinity"] = {
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {
                        "labelSelector": {"matchLabels": {"app": app}},
                        "topologyKey": HOST_KEY,
                    }
                ]
            }
        }
    return spec


def _pod(name, app, node=None, **kw):
    """A bare pod of template `app`: every call builds fresh dicts with
    the same content, as a snapshot import does."""
    spec = _spec(app, **kw)
    if node is not None:
        spec["nodeName"] = node
    return {
        "kind": "Pod",
        "apiVersion": "v1",
        "metadata": {"name": name, "namespace": "default", "labels": {"app": app}},
        "spec": spec,
    }


def _encode(nodes, raw_pods, groups_path):
    """encode_batch over validated pods, through the content-group
    index (as Simulator.run_cluster passes it) or pod by pod."""
    res = ResourceTypes(pods=raw_pods)
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    if groups_path:
        index = wl.ExpandIndex()
        pods = wl.pods_excluding_daemon_sets(res, index=index)
        groups = (np.asarray(index.group_of, dtype=np.int64), index.firsts)
        return oracle, cluster, encode_batch(oracle, cluster, pods, groups=groups)
    pods = wl.pods_excluding_daemon_sets(res)
    return oracle, cluster, encode_batch(oracle, cluster, pods)


@pytest.mark.parametrize("groups_path", [True, False], ids=["groups", "no-groups"])
def test_bound_pods_of_one_template_are_one_class(groups_path):
    nodes = [_node(i) for i in range(12)]
    raw = [_pod(f"web-{i}", "web", node=f"n{i:04d}") for i in range(12)]
    _, _, batch = _encode(nodes, raw, groups_path)
    assert batch.u == 1
    assert batch.class_of_pod.tolist() == [0] * 12
    assert batch.pinned_node.tolist() == list(range(12))
    # one row per class in every [U, N] table
    assert batch.static_feasible.shape == (1, 12)


@pytest.mark.parametrize("groups_path", [True, False], ids=["groups", "no-groups"])
def test_bound_and_loose_pods_of_one_template_share_a_class(groups_path):
    nodes = [_node(i) for i in range(6)]
    raw = [
        _pod("web-0", "web", node="n0003"),
        _pod("web-1", "web"),
        _pod("web-2", "web", node="n0001"),
        _pod("web-3", "web"),
    ]
    _, _, batch = _encode(nodes, raw, groups_path)
    assert batch.u == 1
    assert batch.class_of_pod.tolist() == [0, 0, 0, 0]
    assert batch.pinned_node.tolist() == [3, -1, 1, -1]


@pytest.mark.parametrize("groups_path", [True, False], ids=["groups", "no-groups"])
def test_two_templates_keep_two_classes(groups_path):
    nodes = [_node(i) for i in range(8)]
    raw = [
        _pod(f"p-{i}", "web" if i % 2 else "db", node=f"n{i:04d}",
             cpu="100m" if i % 2 else "200m")
        for i in range(8)
    ]
    _, _, batch = _encode(nodes, raw, groups_path)
    assert batch.u == 2
    cls = batch.class_of_pod.tolist()
    assert cls == [0, 1] * 4
    assert batch.req_mcpu[cls[0]] == 200 and batch.req_mcpu[cls[1]] == 100
    assert batch.pinned_node.tolist() == list(range(8))


def test_counters_advance_by_classes_and_pins():
    nodes = [_node(i) for i in range(10)]
    raw = [_pod(f"web-{i}", "web", node=f"n{i:04d}") for i in range(7)] + [
        _pod("db-0", "db", cpu="300m"),
        _pod("db-1", "db", cpu="300m"),
    ]
    c0 = COUNTERS.get("encode_pod_classes_total")
    p0 = COUNTERS.get("encode_pinned_pods_total")
    _, _, batch = _encode(nodes, raw, groups_path=True)
    assert batch.u == 2
    assert COUNTERS.get("encode_pod_classes_total") - c0 == 2
    assert COUNTERS.get("encode_pinned_pods_total") - p0 == 7
    # both show on /metrics
    from open_simulator_tpu.serve.server import _observatory_lines

    lines = _observatory_lines(COUNTERS.snapshot())
    for name in ("encode_pod_classes_total", "encode_pinned_pods_total"):
        assert f"simon_{name} {COUNTERS.get(name)}" in lines


def test_term_batch_past_the_class_scope_builds_a_kernel_plan():
    """More bound anti-affinity pods of one template than the fused
    kernel's class scope (pallas_scan._MAX_U): with one class per pod
    build_plan rejected the batch and it ran on the XLA scan."""
    count = pallas_scan._MAX_U + 8
    nodes = [_node(i) for i in range(count)]
    raw = [_pod(f"db-{i}", "db", node=f"n{i:04d}", anti=True) for i in range(count)]
    oracle, cluster, batch = _encode(nodes, raw, groups_path=True)
    assert batch.u == 1
    assert batch.pinned_node.tolist() == list(range(count))
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    assert features.ipa and features.pins
    plan = pallas_scan.build_plan(cluster, batch, dyn, features, allow_terms=True)
    assert pallas_scan.last_reject() is None
    assert plan is not None and plan.terms is not None


def _placements(result):
    return {
        p["metadata"]["name"]: ns.node["metadata"]["name"]
        for ns in result.node_status
        for p in ns.pods
    }


def test_simulate_tpu_matches_oracle_over_a_bound_cluster():
    nodes = [_node(i) for i in range(9)]
    running = []
    for i in range(7):  # zone-spread pods, two zones ahead
        running.append(_pod(f"web-run-{i}", "web", node=f"n{(i * 4) % 9:04d}", spread=True))
    for i in range(4):  # hostname anti-affinity pods on their own nodes
        running.append(_pod(f"db-run-{i}", "db", node=f"n{i * 2:04d}", anti=True))
    for i in range(9):  # plain pods, filling some nodes more than others
        running.append(_pod(f"plain-run-{i}", "plain", node=f"n{i % 3:04d}", cpu="900m"))
        # a loose pod of the same template in the same batch: it shares
        # the bound pods' class and must see each pin on its own node
        if i % 3 == 2:
            running.append(_pod(f"plain-new-{i}", "plain", cpu="900m"))
    running.insert(9, _pod("db-new-0", "db", anti=True))
    cluster = ResourceTypes(nodes=nodes, pods=running)

    def deployment(name, replicas, **kw):
        return {
            "kind": "Deployment",
            "apiVersion": "apps/v1",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {
                "replicas": replicas,
                "selector": {"matchLabels": {"app": name}},
                "template": {
                    "metadata": {"labels": {"app": name}},
                    "spec": _spec(name, **kw),
                },
            },
        }

    app = ResourceTypes(
        deployments=[
            deployment("web", 8, spread=True),
            deployment("db", 6, anti=True),
            deployment("plain", 10, cpu="700m"),
        ]
    )

    def run(engine):
        wl.reset_name_counter()
        return simulate(
            copy.deepcopy(cluster), [AppResource("w", copy.deepcopy(app))],
            engine=engine,
        )

    oracle_res = run("oracle")
    c0 = COUNTERS.get("encode_pod_classes_total")
    tpu_res = run("tpu")
    # one class per template in each batch, not one per bound pod
    assert COUNTERS.get("encode_pod_classes_total") - c0 == 6
    failed = lambda r: sorted(u.pod["metadata"]["name"] for u in r.unscheduled_pods)
    assert failed(tpu_res) == failed(oracle_res)
    po, pt = _placements(oracle_res), _placements(tpu_res)
    assert len(po) == len(running) + 24 - len(failed(oracle_res))
    assert pt == po
