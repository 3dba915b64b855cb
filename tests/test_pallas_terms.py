"""Conformance of the fused Pallas kernel's term machinery (inter-pod
affinity, hard/soft topology spread) against the XLA scan, which is
itself conformance-tested against the serial oracle. Runs in Pallas
interpret mode on the CPU mesh."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.models.workloads import reset_name_counter
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
from open_simulator_tpu.scheduler.core import AppResource
from open_simulator_tpu.scheduler.queues import expand_apps
from open_simulator_tpu.scheduler.oracle import Oracle

ZONES = ["a", "b", "c", "d"]


@pytest.fixture(params=["resident", "stream"], autouse=True)
def _terms_layout(request):
    """Every case in this module runs twice: once on the resident VMEM
    term plan and once forcing the streamed-terms layout (HBM state +
    per-pod row gather, pallas_scan.STREAM_FORCE) — the layout the
    kernel auto-selects past the VMEM budget. check_case asserts the
    requested layout was actually built."""
    prev = pallas_scan.STREAM_FORCE
    pallas_scan.STREAM_FORCE = request.param == "stream"
    yield request.param
    pallas_scan.STREAM_FORCE = prev


def make_node(i, zone):
    return {
        "kind": "Node",
        "metadata": {
            "name": f"n{i:03d}",
            "labels": {"kubernetes.io/hostname": f"n{i:03d}", "zone": zone},
        },
        "status": {"allocatable": {"cpu": "8", "memory": "32Gi", "pods": "110"}},
    }


def sts(name, reps, cpu="500m", anti_key=None, aff_key=None, spread=None):
    spec = {
        "containers": [
            {"name": "c", "image": "i", "resources": {"requests": {"cpu": cpu, "memory": "1Gi"}}}
        ]
    }
    affinity = {}
    if anti_key:
        affinity["podAntiAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": {"matchLabels": {"app": name}}, "topologyKey": anti_key}
            ]
        }
    if aff_key:
        affinity["podAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": {"matchLabels": {"grp": "hub"}}, "topologyKey": aff_key}
            ]
        }
    if affinity:
        spec["affinity"] = affinity
    if spread:
        spec["topologySpreadConstraints"] = spread
    labels = {"app": name, "grp": "hub" if aff_key else name}
    return {
        "kind": "StatefulSet",
        "metadata": {"name": name, "namespace": "d", "labels": labels},
        "spec": {
            "replicas": reps,
            "template": {"metadata": {"labels": labels}, "spec": spec},
        },
    }


def check_case(
    nodes,
    workloads,
    existing=None,
    node_valid=None,
    pod_active=None,
    mutate_pods=None,
    skip_out_of_scope=False,
):
    """Run the expanded workload through both the XLA scan and the
    fused kernel (interpret mode) and assert identical placements.
    `mutate_pods` may edit the expanded pod list (e.g. add nodeName
    pins) before encoding; `skip_out_of_scope` turns a kernel-scope
    rejection into a pytest skip (for fuzzed inputs)."""
    reset_name_counter()
    res = ResourceTypes()
    res.stateful_sets = workloads
    pods = expand_apps([AppResource("t", res)], nodes)[0]
    if mutate_pods is not None:
        mutate_pods(pods)
    oracle = Oracle(nodes)
    for p in existing or []:
        oracle.place_existing_pod(p)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    plan = pallas_scan.build_plan(cluster, batch, dyn, features, allow_terms=True)
    if plan is None and skip_out_of_scope:
        pytest.skip("batch out of kernel scope")
    assert plan is not None and plan.terms is not None
    assert plan.terms.cfg.stream == (pallas_scan.STREAM_FORCE is True)
    static = to_scan_static(cluster, batch)
    init = to_scan_state(dyn, batch)
    nv = np.ones(cluster.n, bool) if node_valid is None else node_valid
    if pod_active is None:
        pa = np.ones(len(pods), bool)
    elif isinstance(pod_active, dict):
        # box filled by mutate_pods once the expanded pod count is known
        pa = pod_active.get("pa", np.ones(len(pods), bool))
    else:
        pa = pod_active
    ref, _ = scan_ops.run_scan_masked(
        static,
        init,
        jnp.asarray(batch.class_of_pod),
        jnp.asarray(batch.pinned_node),
        jnp.asarray(nv),
        jnp.asarray(pa),
        features=features,
    )
    got, _ = pallas_scan.run_scan_pallas(
        plan, batch.class_of_pod, pa, nv, pinned=batch.pinned_node,
        interpret=True,
    )
    assert (np.asarray(ref) == got).all()
    return got


def _nodes(n=32):
    return [make_node(i, ZONES[i % 4]) for i in range(n)]


def test_soft_zone_spread():
    placements = check_case(
        _nodes(),
        [
            sts(
                "w1",
                12,
                spread=[
                    {
                        "maxSkew": 1,
                        "topologyKey": "zone",
                        "whenUnsatisfiable": "ScheduleAnyway",
                        "labelSelector": {"matchLabels": {"app": "w1"}},
                    }
                ],
            )
        ],
    )
    assert (placements >= 0).all()


def test_hard_zone_spread():
    check_case(
        _nodes(),
        [
            sts(
                "w2",
                10,
                spread=[
                    {
                        "maxSkew": 2,
                        "topologyKey": "zone",
                        "whenUnsatisfiable": "DoNotSchedule",
                        "labelSelector": {"matchLabels": {"app": "w2"}},
                    }
                ],
            )
        ],
    )


def test_required_affinity_group():
    check_case(_nodes(), [sts("hub", 3, aff_key="zone"), sts("spoke", 9, aff_key="zone")])


def test_mixed_anti_affinity_and_spreads():
    check_case(
        _nodes(),
        [
            sts("a1", 8, anti_key="kubernetes.io/hostname"),
            sts(
                "a2",
                8,
                spread=[
                    {
                        "maxSkew": 1,
                        "topologyKey": "zone",
                        "whenUnsatisfiable": "ScheduleAnyway",
                        "labelSelector": {"matchLabels": {"app": "a2"}},
                    },
                    {
                        "maxSkew": 3,
                        "topologyKey": "kubernetes.io/hostname",
                        "whenUnsatisfiable": "DoNotSchedule",
                        "labelSelector": {"matchLabels": {"app": "a2"}},
                    },
                ],
            ),
        ],
    )


def test_existing_pods_and_scenario_mask():
    existing = [
        {
            "metadata": {"name": f"ex{i}", "namespace": "d", "labels": {"app": "a1"}},
            "spec": {
                "nodeName": f"n{i:03d}",
                "containers": [
                    {"name": "c", "image": "i", "resources": {"requests": {"cpu": "1"}}}
                ],
            },
            "status": {"phase": "Running"},
        }
        for i in range(6)
    ]
    nv = np.ones(32, bool)
    nv[24:] = False
    # anti-affinity vs existing pods: the 6 prefilled hosts are taken
    placements = check_case(
        _nodes(),
        [sts("a1", 10, anti_key="kubernetes.io/hostname")],
        existing=existing,
        node_valid=nv,
    )
    taken = set(range(6))
    assert not (set(placements[placements >= 0].tolist()) & taken)


def test_inactive_pods_commit_nothing():
    pa = np.ones(10, bool)
    pa[3] = False
    pa[7] = False
    placements = check_case(
        _nodes(), [sts("w3", 10, anti_key="zone")], pod_active=pa
    )
    assert placements[3] == pallas_scan.INACTIVE
    assert placements[7] == pallas_scan.INACTIVE


def test_pinned_pods_force_placement():
    """spec.nodeName pins override selection (and commit resources on
    the pinned node even when it would not be selected); a pin outside
    the scenario's node_valid mask makes the pod INACTIVE."""

    def pin(pods):
        # pin pod 0 to node 9; pin pod 1 to node 12, which the
        # scenario mask below disables
        pods[0]["spec"]["nodeName"] = "n009"
        pods[1]["spec"]["nodeName"] = "n012"

    nv = np.ones(16, bool)
    nv[12] = False
    got = check_case(
        _nodes(16), [sts("w", 8, anti_key="zone")], node_valid=nv, mutate_pods=pin
    )
    assert got[0] == 9
    assert got[1] == pallas_scan.INACTIVE


@pytest.mark.parametrize("seed", range(6))
def test_randomized_mixed_conformance(seed):
    """Fuzz: random mixes of anti-affinity / required affinity / hard+
    soft spread / pins / scenario masks must match the XLA scan
    placement-for-placement."""
    rng = np.random.RandomState(seed)
    n = int(rng.randint(12, 40))
    k_zones = int(rng.randint(2, 5))
    nodes = [make_node(i, ZONES[i % k_zones]) for i in range(n)]
    workloads = []
    for w in range(rng.randint(1, 4)):
        name = f"w{w}"
        kind = rng.randint(0, 4)
        kwargs = {}
        if kind == 0:
            kwargs["anti_key"] = rng.choice(["kubernetes.io/hostname", "zone"])
        elif kind == 1:
            kwargs["aff_key"] = "zone"
        elif kind == 2:
            kwargs["spread"] = [
                {
                    "maxSkew": int(rng.randint(1, 4)),
                    "topologyKey": str(rng.choice(["zone", "kubernetes.io/hostname"])),
                    "whenUnsatisfiable": str(
                        rng.choice(["DoNotSchedule", "ScheduleAnyway"])
                    ),
                    "labelSelector": {"matchLabels": {"app": name}},
                }
            ]
        else:
            kwargs["anti_key"] = "zone"
            kwargs["spread"] = [
                {
                    "maxSkew": 1,
                    "topologyKey": "zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": name}},
                }
            ]
        workloads.append(sts(name, int(rng.randint(2, 9)), **kwargs))

    # pod_active needs the expanded pod count, which mutate_pods sees
    # first: pin a couple of pods there and draw the activity mask
    pa_box = {}

    def pin_and_mask(pods):
        for p_i in rng.choice(len(pods), size=min(2, len(pods)), replace=False):
            pods[p_i]["spec"]["nodeName"] = f"n{rng.randint(0, n):03d}"
        pa = rng.rand(len(pods)) > 0.1
        pa_box["pa"] = pa

    nv = rng.rand(n) > 0.15
    nv[0] = True
    check_case(
        nodes,
        workloads,
        node_valid=nv,
        pod_active=pa_box,
        mutate_pods=pin_and_mask,
        skip_out_of_scope=True,
    )


def test_affinity_stress_slice():
    """A small slice of the bench's affinity-stress scenario."""
    from open_simulator_tpu.testing import build_affinity_stress

    reset_name_counter()
    nodes, stss = build_affinity_stress(n_nodes=24, n_sts=6, replicas=4, zones=3)
    res = ResourceTypes()
    res.stateful_sets = stss
    pods = expand_apps([AppResource("t", res)], nodes)[0]
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    assert features.ipa and features.hard_spread and features.soft_spread
    plan = pallas_scan.build_plan(cluster, batch, dyn, features, allow_terms=True)
    assert plan is not None and plan.terms is not None
    static = to_scan_static(cluster, batch)
    init = to_scan_state(dyn, batch)
    ref, _ = scan_ops.run_scan(
        static,
        init,
        jnp.asarray(batch.class_of_pod),
        jnp.asarray(batch.pinned_node),
        features=features,
    )
    got, _ = pallas_scan.run_scan_pallas(
        plan,
        batch.class_of_pod,
        np.ones(len(pods), bool),
        np.ones(cluster.n, bool),
        interpret=True,
    )
    assert (np.asarray(ref) == got).all()


def test_many_classes_beyond_128():
    """Class-column tables span multiple sublane rows when the batch
    has more than 128 pod classes (live-cluster imports are this
    heterogeneous); the kernel must agree with the XLA scan across the
    row boundary."""
    from open_simulator_tpu.testing import build_affinity_stress

    reset_name_counter()
    nodes, stss = build_affinity_stress(n_nodes=24, n_sts=6, replicas=4, zones=3)

    def add_unique_classes(pods):
        # 140 pods with distinct cpu requests -> 140 distinct classes
        # on top of the STS template classes, crossing 128
        import copy

        base = pods[0]
        for i in range(140):
            p = copy.deepcopy(base)
            p["metadata"]["name"] = f"uniq-{i:03d}"
            p["spec"]["containers"][0]["resources"]["requests"]["cpu"] = f"{i + 1}m"
            p["spec"].pop("affinity", None)
            pods.append(p)

    res = ResourceTypes()
    res.stateful_sets = stss
    reset_name_counter()
    pods = expand_apps([AppResource("t", res)], nodes)[0]
    add_unique_classes(pods)
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    assert batch.u > 128, f"scenario only built {batch.u} classes"
    dyn = encode_dynamic(oracle, cluster)
    features = features_of_batch(cluster, batch)
    assert features.ipa
    plan = pallas_scan.build_plan(cluster, batch, dyn, features, allow_terms=True)
    assert plan is not None and plan.terms is not None
    static = to_scan_static(cluster, batch)
    init = to_scan_state(dyn, batch)
    ref, _ = scan_ops.run_scan(
        static,
        init,
        jnp.asarray(batch.class_of_pod),
        jnp.asarray(batch.pinned_node),
        features=features,
    )
    got, _ = pallas_scan.run_scan_pallas(
        plan,
        batch.class_of_pod,
        np.ones(len(pods), bool),
        np.ones(cluster.n, bool),
        pinned=batch.pinned_node,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
