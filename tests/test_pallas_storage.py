"""Conformance of the fused kernel's open-local storage block (VG
Binpack + exclusive-device first-fit + host-f64 score tables,
pallas_scan._build_storage) against the XLA scan, which is itself
conformance-tested against the serial oracle (test_engine_conformance).
Runs in Pallas interpret mode on CPU.

Reference semantics: open-local algo.go:487 (ScoreLVMVolume), 574
(Binpack), ProcessLVMPVCPredicate / ProcessDevicePVC — via ops/scan.py
_local_storage_eval, the conformance target here.
"""

import json

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from open_simulator_tpu.ops import pallas_scan
from open_simulator_tpu.ops import scan as scan_ops
from open_simulator_tpu.ops.encode import (
    encode_batch,
    encode_cluster,
    encode_dynamic,
    features_of_batch,
    to_scan_static,
    to_scan_state,
)
from open_simulator_tpu.scheduler.oracle import Oracle

GI = 1 << 30


def make_node(i, vgs=None, devices=None, cpu="32", storage=True):
    anno = {}
    if storage:
        anno["simon/node-local-storage"] = json.dumps(
            {
                "vgs": vgs
                if vgs is not None
                else [
                    {"name": "a", "capacity": str(100 * GI), "requested": "0"},
                    {"name": "b", "capacity": str(200 * GI), "requested": "0"},
                ],
                "devices": devices
                if devices is not None
                else [
                    {
                        "name": "/dev/vdb",
                        "capacity": str(120 * GI),
                        "mediaType": "ssd",
                        "isAllocated": "false",
                    },
                    {
                        "name": "/dev/vdc",
                        "capacity": str(500 * GI),
                        "mediaType": "hdd",
                        "isAllocated": "false",
                    },
                ],
            }
        )
    return {
        "kind": "Node",
        "metadata": {
            "name": f"n{i:04d}",
            "labels": {"kubernetes.io/hostname": f"n{i:04d}"},
            "annotations": anno,
        },
        "status": {
            "allocatable": {"cpu": cpu, "memory": "128Gi", "pods": "110"},
            "capacity": {"cpu": cpu, "memory": "128Gi", "pods": "110"},
        },
    }


def make_pod(name, vols, cpu="100m"):
    anno = {}
    if vols:
        anno["simon/pod-local-storage"] = json.dumps(
            {
                "volumes": [
                    {
                        "kind": k,
                        "size": str(sz),
                        "scName": f"open-local-{k.lower()}",
                    }
                    for k, sz in vols
                ]
            }
        )
    return {
        "metadata": {
            "name": name,
            "namespace": "t",
            "labels": {},
            "annotations": anno,
        },
        "spec": {
            "containers": [
                {
                    "name": "c",
                    "image": "i",
                    "resources": {
                        "requests": {"cpu": cpu, "memory": "128Mi"}
                    },
                }
            ]
        },
    }


def check_case(nodes, pods, existing=None, node_valid=None, pod_active=None):
    """Both engines on identical inputs; assert identical placements
    and that the kernel plan actually carries the storage block."""
    oracle = Oracle(nodes)
    for p in existing or []:
        oracle.place_existing_pod(p)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    assert features.storage
    plan = pallas_scan.build_plan(cluster, batch, dyn, features)
    assert plan is not None, pallas_scan.last_reject()
    assert plan.store is not None
    nv = np.ones(cluster.n, bool) if node_valid is None else node_valid
    pa = np.ones(len(pods), bool) if pod_active is None else pod_active
    static = to_scan_static(cluster, batch)
    init = to_scan_state(dyn, batch)
    ref, ref_state = scan_ops.run_scan_masked(
        static,
        init,
        jnp.asarray(batch.class_of_pod),
        jnp.asarray(batch.pinned_node),
        jnp.asarray(nv),
        jnp.asarray(pa),
        features=features,
    )
    got, final = pallas_scan.run_scan_pallas(
        plan, batch.class_of_pod, pa, nv, pinned=batch.pinned_node,
        interpret=True,
    )
    ref = np.asarray(ref)
    assert (np.where(ref < 0, -1, ref) == np.where(got < 0, -1, got)).all()
    # the exported final VG usage (capacity vg_util) matches the XLA
    # scan's final state byte-for-byte
    assert (
        final["vg_used"] == np.asarray(ref_state.vg_used)
    ).all()
    return got


def test_lvm_binpack_fills_tightest_vg():
    # Binpack: least free space that fits, so repeated 30Gi volumes
    # drain vg a (100Gi) before b (200Gi); conformance pins the order
    nodes = [make_node(0)]
    pods = [make_pod(f"p{i}", [("LVM", 30 * GI)]) for i in range(9)]
    got = check_case(nodes, pods)
    assert (got[:9] >= 0).sum() == 9  # 3 into a (90), 6 into b (180)
    assert (got == 0).all()


def test_lvm_volume_too_big_fails_node():
    nodes = [make_node(0), make_node(1, vgs=[
        {"name": "big", "capacity": str(400 * GI), "requested": "0"}
    ])]
    pods = [make_pod("p0", [("LVM", 250 * GI)])]
    got = check_case(nodes, pods)
    assert got[0] == 1  # only the 400Gi VG fits


def test_multi_volume_sequential_binpack():
    # volumes of ONE pod interact: the second volume sees the first's
    # hypothetical take
    nodes = [make_node(0)]
    pods = [
        make_pod("p0", [("LVM", 80 * GI), ("LVM", 90 * GI), ("LVM", 150 * GI)]),
        make_pod("p1", [("LVM", 80 * GI), ("LVM", 90 * GI)]),
    ]
    check_case(nodes, pods)


def test_exclusive_devices_first_fit_and_exhaustion():
    nodes = [make_node(i) for i in range(2)]
    pods = [make_pod(f"s{i}", [("SSD", 100 * GI)]) for i in range(3)]
    got = check_case(nodes, pods)
    assert (got >= 0).sum() == 2  # one SSD device per node
    assert got[2] == -1


def test_device_preallocated_excluded():
    nodes = [
        make_node(0, devices=[
            {"name": "/dev/vdb", "capacity": str(120 * GI),
             "mediaType": "ssd", "isAllocated": "true"},
        ]),
        make_node(1),
    ]
    pods = [make_pod("s0", [("SSD", 100 * GI)])]
    got = check_case(nodes, pods)
    assert got[0] == 1


def test_initial_vg_requested_honored():
    nodes = [
        make_node(0, vgs=[
            {"name": "a", "capacity": str(100 * GI),
             "requested": str(95 * GI)},
        ]),
        make_node(1, vgs=[
            {"name": "a", "capacity": str(100 * GI), "requested": "0"},
        ]),
    ]
    pods = [make_pod("p0", [("LVM", 10 * GI)])]
    got = check_case(nodes, pods)
    assert got[0] == 1


def test_non_storage_nodes_reject_storage_pods():
    nodes = [make_node(0, storage=False), make_node(1)]
    pods = [make_pod("p0", [("LVM", GI)]), make_pod("p1", None)]
    got = check_case(nodes, pods)
    assert got[0] == 1


def test_scenario_masks_apply():
    nodes = [make_node(i) for i in range(4)]
    pods = [make_pod(f"p{i}", [("LVM", GI)]) for i in range(6)]
    nv = np.array([False, True, True, False])
    pa = np.array([True, False, True, True, True, False])
    got = check_case(nodes, pods, node_valid=nv, pod_active=pa)
    assert set(got[pa]) <= {1, 2}


def test_existing_pods_do_not_recharge_vgs():
    # pre-bound pods carry their storage usage in the NODE annotation's
    # `requested` field (the reference builds the open-local cache from
    # the cluster snapshot, not by replaying bound pods) — admitting an
    # existing pod must not double-charge, and both engines must agree
    # on the resulting state
    nodes = [make_node(i) for i in range(2)]
    ex = make_pod("ex", [("LVM", 95 * GI), ("LVM", 190 * GI)])
    ex["spec"]["nodeName"] = "n0000"
    pods = [make_pod("p0", [("LVM", 50 * GI)])]
    got = check_case(nodes, pods, existing=[ex])
    assert got[0] == 0  # n0's VGs still read empty, so Binpack stays put


@pytest.mark.parametrize("seed", range(4))
def test_randomized_mixed_conformance(seed):
    rng = np.random.RandomState(seed)
    nodes = []
    for i in range(48):
        vgs = [
            {"name": "a", "capacity": str(int(rng.choice([50, 100])) * GI),
             "requested": str(int(rng.randint(0, 10)) * GI)},
            {"name": "b", "capacity": str(int(rng.choice([100, 200])) * GI),
             "requested": "0"},
        ]
        devices = [
            {"name": "/dev/vdb", "capacity": str(int(rng.choice([80, 120])) * GI),
             "mediaType": "ssd", "isAllocated": "false"},
            {"name": "/dev/vdc", "capacity": str(500 * GI),
             "mediaType": "hdd",
             "isAllocated": "true" if rng.rand() < 0.2 else "false"},
        ]
        nodes.append(
            make_node(i, vgs=vgs, devices=devices, storage=rng.rand() < 0.9)
        )
    shapes = [
        [("LVM", 1 * GI)],
        [("LVM", 5 * GI)],
        [("LVM", 10 * GI), ("LVM", 2 * GI)],
        [("LVM", 8 * GI), ("LVM", 4 * GI), ("LVM", 1 * GI)],
        [("SSD", 100 * GI)],
        [("HDD", 400 * GI)],
        [("LVM", 3 * GI), ("SSD", 60 * GI)],
        None,
    ]
    pods = [
        make_pod(f"p{p:04d}", shapes[int(rng.randint(0, len(shapes)))])
        for p in range(200)
    ]
    check_case(nodes, pods)


def test_unscalable_volume_size_rejects_to_xla():
    """A volume size sharing no useful GCD with the capacities (scale
    ~1) would overflow the kernel's int32 encoding; the plan must
    REJECT (XLA scan carries the batch) rather than wrap and diverge."""
    nodes = [make_node(0)]
    pods = [make_pod("p0", [("LVM", (1 << 31) + 1)])]  # odd byte count
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    plan = pallas_scan.build_plan(cluster, batch, dyn, features)
    assert plan is None
    assert "int32" in (pallas_scan.last_reject() or "")


def test_gpu_and_storage_and_terms_in_one_kernel():
    """All three optional kernel blocks together — gpu device packing,
    the storage block, and affinity terms — in ONE compiled plan (the
    fuzz flavors exercise gpu XOR storage; this pins their coexistence)."""
    from open_simulator_tpu.models import workloads as wl
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.models.workloads import reset_name_counter
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.scheduler.queues import expand_apps
    from open_simulator_tpu.testing import build_affinity_stress, with_node_gpu

    reset_name_counter()
    nodes, stss = build_affinity_stress(
        n_nodes=48, n_sts=6, replicas=10, zones=4
    )
    rng = np.random.RandomState(7)
    for i, node in enumerate(nodes):
        with_node_gpu(2, "32")(node)
        if i % 2 == 0:
            node["metadata"].setdefault("annotations", {})[
                "simon/node-local-storage"
            ] = json.dumps(
                {
                    "vgs": [
                        {"name": "a", "capacity": str(100 * GI),
                         "requested": "0"}
                    ],
                    "devices": [],
                }
            )
    res = ResourceTypes()
    res.stateful_sets = stss
    pods = expand_apps([AppResource("m", res)], nodes)[0]
    import copy

    for i, pod in enumerate(pods):
        k = rng.randint(0, 6)
        if k == 0:
            pod["metadata"] = copy.deepcopy(pod["metadata"])
            pod["metadata"].setdefault("annotations", {})[
                "alibabacloud.com/gpu-mem"
            ] = "8"
        elif k == 1:
            pod["metadata"] = copy.deepcopy(pod["metadata"])
            pod["metadata"].setdefault("annotations", {})[
                "simon/pod-local-storage"
            ] = json.dumps(
                {"volumes": [{"kind": "LVM", "size": str(5 * GI),
                              "scName": "open-local-lvm"}]}
            )
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    assert features.gpu and features.storage and features.terms
    plan = pallas_scan.build_plan(cluster, batch, dyn, features)
    assert plan is not None, pallas_scan.last_reject()
    assert plan.store is not None and plan.g_n and plan.terms is not None
    nv = np.ones(cluster.n, bool)
    pa = np.ones(len(pods), bool)
    static = to_scan_static(cluster, batch)
    init = to_scan_state(dyn, batch)
    ref, _ = scan_ops.run_scan_masked(
        static, init, jnp.asarray(batch.class_of_pod),
        jnp.asarray(batch.pinned_node), jnp.asarray(nv), jnp.asarray(pa),
        features=features,
    )
    got, _ = pallas_scan.run_scan_pallas(
        plan, batch.class_of_pod, pa, nv, pinned=batch.pinned_node,
        interpret=True,
    )
    ref = np.asarray(ref)
    assert (np.where(ref < 0, -1, ref) == np.where(got < 0, -1, got)).all()
