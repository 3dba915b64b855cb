"""CapacitySweep.lower_bound evaluates every count at once from prefix
sums over the candidate-node axis. It must return exactly what the
count-by-count loop over node_valid / pod_active returns, which is kept
here as the reference."""

import numpy as np
import pytest

from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.parallel.sweep import CapacitySweep
from open_simulator_tpu.scheduler.core import AppResource
from open_simulator_tpu.testing import make_fake_node, with_node_local_storage

GI = 1 << 30


def loop_lower_bound(sweep, max_cpu=100, max_mem=100, max_vg=100):
    """The bound as one pass over pods and nodes per count."""
    b, c_enc, d = sweep.batch, sweep.cluster_enc, sweep.dyn
    cls = b.class_of_pod
    req = {
        "mcpu": b.req_mcpu[cls].astype(np.int64),
        "mem": b.req_mem[cls].astype(np.int64),
        "eph": b.req_eph[cls].astype(np.int64),
        "pods": np.ones(len(sweep.pods), dtype=np.int64),
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
    for count in range(0, sweep.max_count + 1):
        valid = sweep.node_valid(count)
        active = sweep.pod_active(valid)
        ok = True
        for r in ("mcpu", "mem", "eph", "pods"):
            if base_used[r] + int(req[r][active].sum()) > int(alloc[r][valid].sum()):
                ok = False
                break
        if ok:
            for r, cap in (("mcpu", max_cpu), ("mem", max_mem), ("vg", max_vg)):
                total_alloc = int(alloc[r][valid].sum())
                if total_alloc == 0:
                    continue
                used = base_used[r] + int(req[r][active].sum())
                if int(used / total_alloc * 100) > cap:
                    ok = False
                    break
        if ok:
            return count
    return sweep.max_count


def _node(name, cpu="4", mem="8Gi", eph=None, vgs=None):
    opts = [with_node_local_storage(vgs)] if vgs else []
    node = make_fake_node(name, cpu, mem, *opts)
    if eph is not None:
        for section in ("allocatable", "capacity"):
            node["status"][section]["ephemeral-storage"] = eph
    return node


def _template(cpu="1", mem="1Gi", eph=None):
    requests = {"cpu": cpu, "memory": mem}
    if eph is not None:
        requests["ephemeral-storage"] = eph
    return {
        "metadata": {"labels": {}, "annotations": {}},
        "spec": {
            "containers": [
                {"name": "c", "image": "i", "resources": {"requests": requests}}
            ]
        },
    }


def _deploy(name, replicas, **tpl):
    template = _template(**tpl)
    template["metadata"]["labels"] = {"app": name}
    return {
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": "lb", "labels": {"app": name}},
        "spec": {"replicas": replicas, "template": template},
    }


def _lvm_statefulset(name, replicas, sizes, **tpl):
    # volumeClaimTemplates on the open-local LVM class become VG requests
    sts = _deploy(name, replicas, **tpl)
    sts["kind"] = "StatefulSet"
    sts["spec"]["volumeClaimTemplates"] = [
        {
            "metadata": {"name": f"v{i}"},
            "spec": {
                "storageClassName": "open-local-lvm",
                "resources": {"requests": {"storage": str(size)}},
            },
        }
        for i, size in enumerate(sizes)
    ]
    return sts


def _daemonset(name, **tpl):
    template = _template(**tpl)
    template["metadata"]["labels"] = {"app": name}
    return {
        "kind": "DaemonSet",
        "metadata": {"name": name, "namespace": "lb", "labels": {"app": name}},
        "spec": {"template": template},
    }


def _running(name, node, cpu="1", mem="1Gi"):
    pod = _template(cpu=cpu, mem=mem)
    pod.update(kind="Pod", apiVersion="v1")
    pod["metadata"].update(name=name, namespace="lb")
    pod["spec"]["nodeName"] = node
    pod["status"] = {"phase": "Running"}
    return pod


def _apps(deployments=(), daemon_sets=(), stateful_sets=()):
    resources = ResourceTypes()
    resources.deployments = list(deployments)
    resources.daemon_sets = list(daemon_sets)
    resources.stateful_sets = list(stateful_sets)
    return [AppResource("lb", resources)]


def _empty_base():
    return ResourceTypes(), _apps([_deploy("web", 10)]), _node("tpl"), 8


def _base_running():
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0"), _node("base-1")]
    cluster.pods = [
        _running(f"run-{i}", f"base-{i % 2}", cpu="1500m", mem="3Gi") for i in range(4)
    ]
    return cluster, _apps([_deploy("web", 12)]), _node("tpl"), 10


def _daemonsets():
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0")]
    apps = _apps([_deploy("web", 9)], [_daemonset("agent", cpu="500m", mem="512Mi")])
    return cluster, apps, _node("tpl"), 8


def _daemonset_heavier_than_node():
    # each candidate brings a DaemonSet pod that asks for more cpu than
    # the node adds, so the cpu share grows with the count
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0", cpu="16", mem="32Gi")]
    apps = _apps([_deploy("web", 8)], [_daemonset("hog", cpu="5", mem="1Gi")])
    return cluster, apps, _node("tpl"), 5


def _open_local():
    # the base node's VG already has space taken, which the encoded
    # base usage carries
    vgs = [{"name": "a", "capacity": str(100 * GI), "requested": "0"}]
    taken = [{"name": "a", "capacity": str(100 * GI), "requested": str(60 * GI)}]
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0", cpu="16", mem="64Gi", vgs=taken)]
    apps = _apps(
        stateful_sets=[_lvm_statefulset("db", 12, [30 * GI, 10 * GI], cpu="100m", mem="128Mi")]
    )
    return cluster, apps, _node("tpl", cpu="16", mem="64Gi", vgs=vgs), 12


def _ephemeral():
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0", cpu="32", mem="64Gi", eph="20Gi")]
    apps = _apps([_deploy("scratch", 14, cpu="100m", mem="128Mi", eph="6Gi")])
    return cluster, apps, _node("tpl", cpu="32", mem="64Gi", eph="20Gi"), 8


def _infeasible():
    return ResourceTypes(), _apps([_deploy("web", 40)]), _node("tpl"), 3


def _no_spec():
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0")]
    return cluster, _apps([_deploy("web", 6)]), None, 5


def _zero_count():
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0")]
    return cluster, _apps([_deploy("web", 2)]), _node("tpl"), 0


def _totals_past_2_53():
    # memory totals of several PiB, where float64 stops holding every
    # integer and the cap is decided in Python ints
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0", cpu="64", mem="6Pi")]
    apps = _apps([_deploy("big", 20, cpu="100m", mem="1Pi")])
    return cluster, apps, _node("tpl", cpu="64", mem="3Pi"), 8


# 303187618361319720 / 541406461359499547 is 55.99...% exactly, but
# 56% once both are rounded to float64: under the 55% cap of CAPS it
# passes in ints and fails in floats
ROUNDING_USED, ROUNDING_ALLOC = 303187618361319720, 541406461359499547


def _share_rounds_past_2_53():
    cluster = ResourceTypes()
    cluster.nodes = [_node("base-0", mem=str(ROUNDING_ALLOC))]
    apps = _apps([_deploy("one", 1, cpu="100m", mem=str(ROUNDING_USED))])
    return cluster, apps, _node("tpl", mem="0"), 2


CASES = {
    "empty-base": _empty_base,
    "base-running": _base_running,
    "daemonsets": _daemonsets,
    "daemonset-heavier-than-node": _daemonset_heavier_than_node,
    "open-local-vg": _open_local,
    "ephemeral": _ephemeral,
    "infeasible": _infeasible,
    "no-spec": _no_spec,
    "max-count-zero": _zero_count,
    "totals-past-2-53": _totals_past_2_53,
    "share-rounds-past-2-53": _share_rounds_past_2_53,
}
CAPS = {"no-caps": (100, 100, 100), "caps": (40, 55, 30)}


@pytest.fixture(scope="module")
def sweeps():
    built = {}

    def get(case):
        if case not in built:
            cluster, apps, spec, max_count = CASES[case]()
            built[case] = CapacitySweep(cluster, apps, spec, max_count=max_count)
        return built[case]

    return get


@pytest.mark.parametrize("caps", sorted(CAPS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_lower_bound_matches_count_loop(sweeps, case, caps):
    sweep = sweeps(case)
    max_cpu, max_mem, max_vg = CAPS[caps]
    assert sweep.lower_bound(max_cpu, max_mem, max_vg) == loop_lower_bound(
        sweep, max_cpu, max_mem, max_vg
    )


def test_lower_bound_cases_reach_their_branches(sweeps):
    """The cases above hold what they are named for, so the equivalence
    is checked on each branch of the bound."""
    # running pods are pinned pods of the sweep, active at every count
    running = sweeps("base-running")
    assert running.had_node_name.sum() == 4
    assert (running._ds_target[running.had_node_name] < running.n_base).all()
    assert int(sweeps("open-local-vg").dyn.vg_used.sum()) > 0
    ds = sweeps("daemonsets")
    assert (ds._ds_target >= ds.n_base).sum() == ds.max_count
    assert int(sweeps("open-local-vg").batch.lvm_sizes.sum()) > 0
    assert int(sweeps("ephemeral").batch.req_eph.sum()) > 0
    assert int(sweeps("totals-past-2-53").cluster_enc.alloc_mem.sum()) >= 2**53
    assert sweeps("no-spec").max_count == 0
    share = ROUNDING_USED / ROUNDING_ALLOC * 100
    assert int(share) == 55 < int(float(ROUNDING_USED) / float(ROUNDING_ALLOC) * 100)
    assert sweeps("share-rounds-past-2-53").lower_bound(*CAPS["caps"]) == 0
    # caps bind before fit: the capped bound lies above the uncapped one
    assert sweeps("empty-base").lower_bound(40, 55, 30) > sweeps("empty-base").lower_bound()
    vg = sweeps("open-local-vg")
    assert vg.lower_bound(100, 100, 30) > vg.lower_bound()
    # infeasible even at max_count, with and without caps
    inf = sweeps("infeasible")
    assert loop_lower_bound(inf) == inf.max_count
    assert inf.lower_bound() == inf.max_count
    # more candidates only hurt once the DaemonSet outgrows its node
    hog = sweeps("daemonset-heavier-than-node")
    assert hog.lower_bound() == 0 == loop_lower_bound(hog)


def test_lower_bound_matches_count_loop_at_scale():
    """A few thousand pods over a few thousand candidate nodes; the
    count only, since timing on a shared CPU says nothing."""
    cluster = ResourceTypes()
    cluster.nodes = [_node(f"base-{i}") for i in range(4)]
    apps = _apps(
        [_deploy("web", 4000, cpu="1", mem="512Mi"), _deploy("db", 600, cpu="3", mem="6Gi")]
    )
    sweep = CapacitySweep(cluster, apps, _node("tpl"), max_count=2500)
    for caps in ((100, 100, 100), (70, 90, 100)):
        assert sweep.lower_bound(*caps) == loop_lower_bound(sweep, *caps)
