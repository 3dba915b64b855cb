"""Conformance: the JAX scan engine must reproduce the serial oracle's
placements pod-for-pod (the bit-match contract from SURVEY.md §7).
"""

import os
import random

import pytest

from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.models.cluster import cluster_from_config_dir
from open_simulator_tpu.models.decode import load_directory
from open_simulator_tpu.scheduler.core import simulate, AppResource

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEMO = os.path.join(_REPO, "example", "cluster", "demo")
GPUSHARE = os.path.join(_REPO, "example", "cluster", "gpushare")
GPUSHARE_APP = os.path.join(_REPO, "example", "application", "gpushare")
# hand-written stand-ins for the reference's example apps
APPS = os.path.join(_REPO, "tests", "data", "reference", "application")


def _placements(result):
    out = {}
    for ns in result.node_status:
        for p in ns.pods:
            out[p["metadata"]["name"]] = ns.node["metadata"]["name"]
    return out


def _failed(result):
    return sorted(up.pod["metadata"]["name"] for up in result.unscheduled_pods)


def _compare(cluster, apps):
    from open_simulator_tpu.models.workloads import reset_name_counter

    reset_name_counter()
    res_oracle = simulate(cluster, apps, engine="oracle")
    reset_name_counter()
    res_tpu = simulate(cluster, apps, engine="tpu")
    assert _failed(res_oracle) == _failed(res_tpu)
    po, pt = _placements(res_oracle), _placements(res_tpu)
    assert po.keys() == pt.keys()
    diff = {k: (po[k], pt[k]) for k in po if po[k] != pt[k]}
    assert not diff, f"{len(diff)} placement mismatches: {dict(list(diff.items())[:5])}"


def test_demo1_simple_conformance():
    cluster = cluster_from_config_dir(DEMO)
    _compare(cluster, [AppResource("simple", load_directory(f"{APPS}/simple"))])


def test_demo1_overflow_conformance():
    cluster = cluster_from_config_dir(DEMO)
    apps = [
        AppResource("simple", load_directory(f"{APPS}/simple")),
        AppResource("more_pods", load_directory(f"{APPS}/more_pods")),
    ]
    _compare(cluster, apps)


def test_gpushare_conformance():
    cluster = cluster_from_config_dir(GPUSHARE)
    _compare(cluster, [AppResource("gpushare", load_directory(GPUSHARE_APP))])


def _random_node(rng, i):
    labels = {"kubernetes.io/hostname": f"rn-{i}", "zone": f"z{rng.randint(0, 2)}"}
    node = {
        "kind": "Node",
        "metadata": {"name": f"rn-{i}", "labels": labels},
        "status": {
            "allocatable": {
                "cpu": str(rng.choice([2, 4, 8, 16])),
                "memory": f"{rng.choice([4, 8, 16, 32])}Gi",
                "pods": "110",
            }
        },
    }
    if rng.random() < 0.3:
        node["metadata"]["labels"]["role"] = "special"
    if rng.random() < 0.25:
        node["spec"] = {
            "taints": [{"key": "dedicated", "value": "infra", "effect": "NoSchedule"}]
        }
    if rng.random() < 0.2:
        node["status"]["allocatable"]["alibabacloud.com/gpu-count"] = str(rng.choice([2, 4]))
        node["status"]["allocatable"]["alibabacloud.com/gpu-mem"] = f"{rng.choice([16, 32])}Gi"
        node["status"]["capacity"] = dict(node["status"]["allocatable"])
    return node


def _random_workload(rng, i):
    cpu = rng.choice(["100m", "250m", "500m", "1", "1500m"])
    mem = rng.choice(["128Mi", "256Mi", "512Mi", "1Gi", "2Gi"])
    spec = {
        "containers": [
            {
                "name": "c",
                "image": f"img-{rng.randint(0, 5)}",
                "resources": {"requests": {"cpu": cpu, "memory": mem}},
            }
        ]
    }
    if rng.random() < 0.3:
        spec["tolerations"] = [{"key": "dedicated", "operator": "Exists"}]
    if rng.random() < 0.25:
        spec["nodeSelector"] = {"zone": f"z{rng.randint(0, 2)}"}
    if rng.random() < 0.15:
        spec["affinity"] = {
            "nodeAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {
                        "weight": rng.randint(1, 100),
                        "preference": {
                            "matchExpressions": [
                                {"key": "role", "operator": "In", "values": ["special"]}
                            ]
                        },
                    }
                ]
            }
        }
    deploy = {
        "kind": "Deployment",
        "metadata": {"name": f"wl-{i}", "namespace": "rand", "labels": {"app": f"wl-{i}"}},
        "spec": {"replicas": rng.randint(1, 6), "template": {"spec": spec}},
    }
    return deploy


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_randomized_conformance(seed):
    rng = random.Random(seed)
    cluster = ResourceTypes()
    cluster.nodes = [_random_node(rng, i) for i in range(rng.randint(4, 12))]
    resources = ResourceTypes()
    resources.deployments = [_random_workload(rng, i) for i in range(rng.randint(3, 8))]
    if rng.random() < 0.5:
        resources.pods = [
            {
                "kind": "Pod",
                "metadata": {
                    "name": "gpupod",
                    "namespace": "rand",
                    "annotations": {
                        "alibabacloud.com/gpu-mem": "4Gi",
                        "alibabacloud.com/gpu-count": "1",
                    },
                },
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "gpu-img",
                            "resources": {"requests": {"cpu": "1", "memory": "1Gi"}},
                        }
                    ]
                },
            }
        ]
    _compare(cluster, [AppResource("rand", resources)])


def _storage_node(rng, i):
    import json as _json

    node = _random_node(rng, 100 + i)
    vgs = [
        {"name": f"vg{j}", "capacity": str(rng.choice([50, 100, 200]) * 1024**3), "requested": "0"}
        for j in range(rng.randint(1, 3))
    ]
    devices = [
        {
            "name": f"/dev/vd{j}",
            "device": f"/dev/vd{j}",
            "capacity": str(rng.choice([100, 200]) * 1024**3),
            "mediaType": rng.choice(["ssd", "hdd"]),
            "isAllocated": "false",
        }
        for j in range(rng.randint(0, 3))
    ]
    node["metadata"].setdefault("annotations", {})[
        "simon/node-local-storage"
    ] = _json.dumps({"vgs": vgs, "devices": devices})
    return node


def _storage_sts(rng, i):
    scs = ["open-local-lvm", "open-local-device-ssd", "open-local-device-hdd"]
    vcts = [
        {
            "spec": {
                "storageClassName": rng.choice(scs),
                "resources": {"requests": {"storage": f"{rng.choice([10, 40, 80])}Gi"}},
            }
        }
        for _ in range(rng.randint(1, 2))
    ]
    return {
        "kind": "StatefulSet",
        "metadata": {"name": f"sts-{i}", "namespace": "st", "labels": {"app": f"sts-{i}"}},
        "spec": {
            "replicas": rng.randint(1, 5),
            "template": {
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "db",
                            "resources": {"requests": {"cpu": "500m", "memory": "1Gi"}},
                        }
                    ]
                }
            },
            "volumeClaimTemplates": vcts,
        },
    }


@pytest.mark.parametrize("seed", [10, 11, 12, 13])
def test_local_storage_conformance(seed):
    rng = random.Random(seed)
    cluster = ResourceTypes()
    cluster.nodes = [_storage_node(rng, i) for i in range(rng.randint(3, 8))] + [
        _random_node(rng, i) for i in range(2)
    ]
    resources = ResourceTypes()
    resources.stateful_sets = [_storage_sts(rng, i) for i in range(rng.randint(2, 5))]
    resources.deployments = [_random_workload(rng, 50)]
    _compare(cluster, [AppResource("storage", resources)])


def _affinity_sts(rng, i):
    """StatefulSet with random required/preferred (anti)affinity and
    topology spread — the BASELINE.json stress shape."""
    name = f"asts-{i}"
    spec = {
        "containers": [
            {
                "name": "c",
                "image": "db",
                "resources": {
                    "requests": {"cpu": rng.choice(["250m", "500m", "1"]), "memory": "512Mi"}
                },
            }
        ]
    }
    affinity = {}
    kind = rng.random()
    selector = {"matchLabels": {"app": name}}
    if kind < 0.45:
        affinity["podAntiAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {
                    "labelSelector": selector,
                    "topologyKey": rng.choice(["kubernetes.io/hostname", "zone"]),
                }
            ]
        }
    elif kind < 0.7:
        affinity["podAntiAffinity"] = {
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {
                    "weight": rng.randint(1, 100),
                    "podAffinityTerm": {
                        "labelSelector": selector,
                        "topologyKey": "kubernetes.io/hostname",
                    },
                }
            ]
        }
    elif kind < 0.85:
        affinity["podAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": selector, "topologyKey": "zone"}
            ]
        }
    else:
        affinity["podAffinity"] = {
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {
                    "weight": rng.randint(1, 100),
                    "podAffinityTerm": {
                        "labelSelector": {"matchLabels": {"app": f"asts-{max(0, i - 1)}"}},
                        "topologyKey": "zone",
                    },
                }
            ]
        }
    if affinity:
        spec["affinity"] = affinity
    if rng.random() < 0.5:
        spec["topologySpreadConstraints"] = [
            {
                "maxSkew": rng.choice([1, 2]),
                "topologyKey": rng.choice(["zone", "kubernetes.io/hostname"]),
                "whenUnsatisfiable": rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
                "labelSelector": selector,
            }
        ]
    return {
        "kind": "StatefulSet",
        "metadata": {"name": name, "namespace": "aff", "labels": {"app": name}},
        "spec": {"replicas": rng.randint(2, 6), "template": {"spec": spec}},
    }


@pytest.mark.parametrize("seed", [20, 21, 22, 23, 24, 25])
def test_affinity_spread_conformance(seed):
    rng = random.Random(seed)
    cluster = ResourceTypes()
    cluster.nodes = [_random_node(rng, i) for i in range(rng.randint(5, 12))]
    resources = ResourceTypes()
    resources.stateful_sets = [_affinity_sts(rng, i) for i in range(rng.randint(3, 8))]
    resources.deployments = [_random_workload(rng, 70)]
    _compare(cluster, [AppResource("aff", resources)])


def test_affinity_across_apps_sees_existing_pods():
    """Terms of pods placed by an earlier app must constrain a later
    app (existing-pod anti-affinity + preferred contributions)."""
    rng = random.Random(99)
    cluster = ResourceTypes()
    cluster.nodes = [_random_node(rng, i) for i in range(8)]
    first = ResourceTypes()
    first.stateful_sets = [_affinity_sts(rng, 0), _affinity_sts(rng, 1)]
    second = ResourceTypes()
    second.stateful_sets = [_affinity_sts(rng, 2)]
    second.deployments = [_random_workload(rng, 71)]
    _compare(
        cluster,
        [AppResource("first", first), AppResource("second", second)],
    )


def test_run_scan_callable_under_external_jit():
    """run_scan with no explicit features must still work when an
    external caller wraps it in jax.jit (features_of falls back to the
    ungated ALL_FEATURES scan), and produce the same placements as the
    specialized direct call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from open_simulator_tpu.ops import scan as scan_ops
    from open_simulator_tpu.ops.encode import (
        encode_batch,
        encode_cluster,
        encode_dynamic,
        to_scan_static,
        to_scan_state,
    )
    from open_simulator_tpu.scheduler.oracle import Oracle

    rng = random.Random(7)
    nodes = [_random_node(rng, i) for i in range(6)]
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    pods = []
    from open_simulator_tpu.models import workloads as wl

    res = ResourceTypes()
    res.deployments = [_random_workload(rng, i) for i in range(3)]
    pods = wl.generate_valid_pods_from_app("t", res, nodes)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    static = to_scan_static(cluster, batch)
    init = to_scan_state(dyn, batch)
    class_arr = jnp.asarray(batch.class_of_pod)
    pinned_arr = jnp.asarray(batch.pinned_node)

    direct, _ = scan_ops.run_scan(static, init, class_arr, pinned_arr)

    @jax.jit
    def wrapped(static, init, class_arr, pinned_arr):
        placements, _ = scan_ops.run_scan(static, init, class_arr, pinned_arr)
        return placements

    jitted = wrapped(static, init, class_arr, pinned_arr)
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(jitted))


def test_storage_bench_scenario_conforms():
    """The SIMON_BENCH=storage builder (bench.build_storage_scenario)
    at toy scale: scan placements must match the serial oracle on the
    open-local VG binpack + exclusive-device path, so the recorded
    bench number is backed by the same conformance as the other
    scenarios."""
    import bench
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.scheduler.core import AppResource, simulate

    nodes, pods = bench.build_storage_scenario(n_nodes=12, n_pods=40)
    cluster = ResourceTypes()
    cluster.nodes = nodes
    res = ResourceTypes()
    res.pods = pods
    apps = [AppResource("stor", res)]
    serial = simulate(cluster, apps, engine="oracle")

    nodes, pods = bench.build_storage_scenario(n_nodes=12, n_pods=40)
    cluster = ResourceTypes()
    cluster.nodes = nodes
    res = ResourceTypes()
    res.pods = pods
    tpu = simulate(cluster, [AppResource("stor", res)], engine="tpu")

    def placements(r):
        return {
            p["metadata"]["name"]: ns.node["metadata"]["name"]
            for ns in r.node_status
            for p in ns.pods
        }

    assert placements(serial) == placements(tpu)
    assert sorted(u.pod["metadata"]["name"] for u in serial.unscheduled_pods) == sorted(
        u.pod["metadata"]["name"] for u in tpu.unscheduled_pods
    )
    # the toy scale still exercised both volume kinds
    assert any("LVM" in str(p["metadata"]["annotations"]) for p in pods)
    assert any("SSD" in str(p["metadata"]["annotations"]) for p in pods)
