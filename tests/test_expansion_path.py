"""One expansion path from workload to kernel batch: simulate, the plan
sweep, serve and the twin take an app's pods, their queue order and
their content groups from scheduler/queues.expand_apps. Each entry
point must see the same pod names in the same order, in the same
groups, as an independent reading of the reference's pipeline:
expansion (GenerateValidPodsFromAppResources), then the stable
affinity and toleration sorts (pkg/algo/affinity.go, toleration.go)."""

import copy

import pytest

from open_simulator_tpu.models import workloads as wl
from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.scheduler import queues
from open_simulator_tpu.scheduler.core import AppResource, simulate

HOST_KEY = "kubernetes.io/hostname"


def _node(i):
    name = f"n{i}"
    node = {
        "kind": "Node",
        "metadata": {"name": name, "labels": {HOST_KEY: name, "disk": "ssd" if i % 2 else "hdd"}},
        "status": {"allocatable": {"cpu": "8", "memory": "32Gi", "pods": "110"}},
    }
    if i == 0:
        node["spec"] = {"taints": [{"key": "dedicated", "value": "infra", "effect": "NoSchedule"}]}
    return node


def _spec(cpu="100m", selector=None, tolerate=False):
    spec = {
        "containers": [
            {"name": "c", "image": "pause", "resources": {"requests": {"cpu": cpu, "memory": "64Mi"}}}
        ]
    }
    if selector:
        spec["nodeSelector"] = selector
    if tolerate:
        spec["tolerations"] = [{"key": "dedicated", "operator": "Exists", "effect": "NoSchedule"}]
    return spec


def _deploy(name, replicas, **kw):
    return {
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": "default", "labels": {"app": name}},
        "spec": {
            "replicas": replicas,
            "selector": {"matchLabels": {"app": name}},
            "template": {"metadata": {"labels": {"app": name}}, "spec": _spec(**kw)},
        },
    }


def _bare(name, node=None, **kw):
    spec = _spec(**kw)
    if node is not None:
        spec["nodeName"] = node
    return {"kind": "Pod", "metadata": {"name": name, "namespace": "default"}, "spec": spec}


def _app():
    res = ResourceTypes()
    res.deployments = [
        _deploy("plain", 3),
        _deploy("picky", 2, selector={"disk": "ssd"}),
        _deploy("tolerant", 2, tolerate=True),
        _deploy("both", 2, selector={"disk": "hdd"}, tolerate=True),
    ]
    res.pods = [
        _bare("bound-0", node="n1"),
        _bare("loose-0"),
        _bare("bound-1", node="n2"),
        _bare("loose-1"),
        _bare("loose-sel", selector={"disk": "ssd"}),
    ]
    res.daemon_sets = [
        {
            "kind": "DaemonSet",
            "metadata": {"name": "agent", "namespace": "kube-system"},
            "spec": {"template": {"metadata": {"labels": {"app": "agent"}}, "spec": _spec(cpu="50m", tolerate=True)}},
        }
    ]
    return AppResource("mixed", res)


def _cluster():
    return ResourceTypes(nodes=[_node(i) for i in range(4)])


def _expected():
    """Names in queue order and each pod's group (named by its first),
    from the reference's per-pod pipeline."""
    wl.reset_name_counter()
    index = wl.ExpandIndex()
    app = _app()
    pods = wl.generate_valid_pods_from_app(app.name, app.resource, _cluster().nodes, index)
    first_of = {
        p["metadata"]["name"]: index.firsts[g]["metadata"]["name"]
        for p, g in zip(pods, index.group_of)
    }
    pods = sorted(pods, key=lambda p: p["spec"].get("nodeSelector") is None)
    pods = sorted(pods, key=lambda p: p["spec"].get("tolerations") is None)
    names = [p["metadata"]["name"] for p in pods]
    return names, [first_of[n] for n in names]


def _run_simulate():
    wl.reset_name_counter()
    simulate(_cluster(), [_app()], engine="tpu")


def _run_sweep():
    from open_simulator_tpu.parallel.sweep import CapacitySweep

    wl.reset_name_counter()
    CapacitySweep(_cluster(), [_app()], None, 0)


def _run_serve():
    from open_simulator_tpu.serve.session import Session, WhatIfRequest

    session = Session(_cluster())
    (reply,) = session.evaluate_batch([WhatIfRequest(apps=[_app()])])
    assert reply.status == 200


def _run_twin():
    from open_simulator_tpu.twin import queries
    from open_simulator_tpu.twin.mirror import ClusterMirror, FeedSource

    mirror = ClusterMirror(_cluster(), FeedSource([], batch=8), engine="tpu")
    queries.whatif(mirror, [_app()])


@pytest.mark.parametrize(
    "entry", [_run_simulate, _run_sweep, _run_serve, _run_twin],
    ids=["simulate", "sweep", "serve", "twin"],
)
def test_entry_points_share_one_expansion(entry, monkeypatch):
    seen = []
    real = queues.expand_apps

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        seen.append(copy.deepcopy(out[:2]))
        return out

    monkeypatch.setattr(queues, "expand_apps", spy)
    entry()
    assert len(seen) == 1
    pods, (group_of, firsts) = seen[0]
    names, first_names = _expected()
    assert [p["metadata"]["name"] for p in pods] == names
    assert [firsts[g]["metadata"]["name"] for g in group_of.tolist()] == first_names
    # the groups are content groups: fewer than the pods
    assert len(firsts) < len(pods)
