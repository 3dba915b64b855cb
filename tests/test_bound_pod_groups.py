"""Bound bare pods of one template are one expansion group: the intern
key of `workloads.pod_from_pod` holds whether a pod is bound, not the
node it names, so a running cluster of N bound pods from T templates
validates T pods, clones the rest with their own spec.nodeName, and
every per-group consumer reads the pin per pod (ops/encode.py
group_pins: the batch pins and the dangling mask of
core._scan_and_commit)."""

import copy

import numpy as np
import pytest

from open_simulator_tpu.models import workloads as wl
from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.ops.encode import encode_batch, encode_cluster
from open_simulator_tpu.scheduler import core as core_mod
from open_simulator_tpu.scheduler.core import AppResource, simulate
from open_simulator_tpu.scheduler.oracle import Oracle
from open_simulator_tpu.utils.trace import COUNTERS

ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"


def _node(i, cpu="4"):
    name = f"n{i:04d}"
    return {
        "kind": "Node",
        "metadata": {"name": name, "labels": {HOST_KEY: name, ZONE_KEY: f"z{i % 3}"}},
        "status": {"allocatable": {"cpu": cpu, "memory": "32Gi", "pods": "110"}},
    }


def _spec(app, cpu="100m", spread=False, anti=False, priority=None):
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
                    {"labelSelector": {"matchLabels": {"app": app}}, "topologyKey": HOST_KEY}
                ]
            }
        }
    if priority is not None:
        spec["priority"] = priority
    return spec


def _pod(name, app, node=None, status=None, **kw):
    """A bare pod of template `app`: every call builds fresh dicts with
    the same content, as a snapshot import does."""
    spec = _spec(app, **kw)
    if node is not None:
        spec["nodeName"] = node
    pod = {
        "kind": "Pod",
        "apiVersion": "v1",
        "metadata": {"name": name, "namespace": "default", "labels": {"app": app}},
        "spec": spec,
    }
    if status is not None:
        pod["status"] = status
    return pod


def _expand(raw):
    index = wl.ExpandIndex()
    pods = wl.pods_excluding_daemon_sets(ResourceTypes(pods=raw), index=index)
    return pods, index


def test_bound_pods_of_one_template_are_one_group():
    raw = [
        _pod(f"web-{i}", "web", node=f"n{i:04d}", status={"phase": "Running"})
        for i in range(10)
    ]
    pods, index = _expand(raw)
    assert len(index.firsts) == 1
    assert index.group_of == [0] * 10
    assert [p["spec"]["nodeName"] for p in pods] == [f"n{i:04d}" for i in range(10)]
    assert [p["metadata"]["name"] for p in pods] == [f"web-{i}" for i in range(10)]
    # each clone owns the dicts the binder writes
    assert len({id(p["spec"]) for p in pods}) == 10
    assert len({id(p["status"]) for p in pods}) == 10
    pods[3]["status"]["phase"] = "Pending"
    assert [p["status"]["phase"] for p in pods].count("Running") == 9
    # the raw input is untouched
    assert raw[3]["status"] == {"phase": "Running"}


def test_bound_and_loose_pods_of_one_template_are_two_groups():
    raw = [
        _pod("web-0", "web", node="n0003"),
        _pod("web-1", "web"),
        _pod("web-2", "web", node="n0001"),
        _pod("web-3", "web"),
        _pod("web-4", "web", node="n0007"),
    ]
    pods, index = _expand(raw)
    assert index.group_of == [0, 1, 0, 1, 0]
    assert index.firsts[0]["spec"]["nodeName"] == "n0003"
    assert "nodeName" not in index.firsts[1]["spec"]
    assert [p["spec"].get("nodeName") for p in pods] == [
        "n0003", None, "n0001", None, "n0007"
    ]


def test_type_distinct_content_stays_in_separate_groups():
    """`==` merges 1, True and 1.0; the JSON key never did, and the
    bound pods of each stay one group with their own nodes."""
    values = [1, True, 1.0, 1, True, 1.0]
    raw = [
        _pod(f"web-{i}", "web", node=f"n{i:04d}", status={"phase": "Running", "x": v})
        for i, v in enumerate(values)
    ]
    pods, index = _expand(raw)
    assert index.group_of == [0, 1, 2, 0, 1, 2]
    for p, v in zip(pods, values):
        assert type(p["status"]["x"]) is type(v)
    assert [p["spec"]["nodeName"] for p in pods] == [f"n{i:04d}" for i in range(6)]


def test_encode_batch_gives_each_pod_its_own_pin():
    nodes = [_node(i) for i in range(8)]
    raw = [_pod(f"web-{i}", "web", node=f"n{(5 * i) % 8:04d}") for i in range(8)]
    raw.insert(2, _pod("web-loose", "web"))
    raw.append(_pod("web-gone", "web", node="n9999"))
    pods, index = _expand(raw)
    assert len(index.firsts) == 2
    oracle = Oracle(nodes)
    batch = encode_batch(
        oracle, encode_cluster(oracle), pods,
        groups=(np.asarray(index.group_of, dtype=np.int64), index.firsts),
    )
    assert batch.u == 1
    expect = [(5 * i) % 8 for i in range(8)]
    expect.insert(2, -1)
    expect.append(-1)
    assert batch.pinned_node.tolist() == expect


def _placements(result):
    return {
        p["metadata"]["name"]: ns.node["metadata"]["name"]
        for ns in result.node_status
        for p in ns.pods
    }


@pytest.mark.parametrize("first_known", [True, False], ids=["first-known", "first-dangling"])
def test_group_mixing_known_and_unknown_pins_drops_only_the_dangling(
    first_known, monkeypatch
):
    nodes = [_node(i) for i in range(4)]
    names = ["n0001", "gone-a", "n0002", "gone-b", "n0001"]
    if not first_known:
        names = names[1:] + names[:1]
    raw = [_pod(f"web-{i}", "web", node=n, cpu="1500m") for i, n in enumerate(names)]
    pods, index = _expand(copy.deepcopy(raw))
    assert len(index.firsts) == 1
    # loose pods in the same batch, exactly as many 1-cpu pods as the
    # known pins leave room for: a dangling pod counted on a node, or a
    # pin read from another pod, moves them
    raw += [_pod(f"new-{i}", "new", cpu="1") for i in range(11)]

    def run(engine):
        wl.reset_name_counter()
        return simulate(
            ResourceTypes(nodes=copy.deepcopy(nodes), pods=copy.deepcopy(raw)), [],
            engine=engine,
        )

    serial = run("oracle")
    # the running batch through the engine too
    monkeypatch.setattr(core_mod, "MIN_SCAN_RUN", 1)
    tpu = run("tpu")
    placed = {n: v for n, v in _placements(tpu).items() if n.startswith("web-")}
    assert placed == {
        f"web-{i}": n for i, n in enumerate(names) if not n.startswith("gone")
    }
    assert not serial.unscheduled_pods and not tpu.unscheduled_pods
    assert _placements(tpu) == _placements(serial)


def _outcome(res):
    return (
        {
            st.node["metadata"]["name"]: [p["metadata"]["name"] for p in st.pods]
            for st in res.node_status
        },
        [(ev.victim["metadata"]["name"], ev.node_name, ev.preemptor)
         for ev in res.preemptions],
        sorted((u.pod["metadata"]["name"], u.reason) for u in res.unscheduled_pods),
    )


@pytest.mark.parametrize("terms", [False, True], ids=["plain", "terms"])
def test_simulate_tpu_matches_oracle_over_a_bound_cluster(terms, monkeypatch):
    """Bound plain, prioritized and (with `terms`) spread and
    anti-affinity pods, then high-priority pods that fit only by
    preempting: on the device dry run without terms, through the
    serial escape with them."""
    nodes = [_node(i, cpu="2") for i in range(6)]
    running = []
    for i in range(12):  # two low pods a node
        running.append(_pod(f"low-{i}", "low", node=f"n{i % 6:04d}", cpu="600m", priority=0))
    for i in range(3):  # a prioritized pod on half the nodes
        running.append(_pod(f"mid-{i}", "mid", node=f"n{2 * i:04d}", cpu="300m", priority=50))
    if terms:
        for i in range(4):
            running.append(_pod(f"spr-{i}", "spr", node=f"n{(i * 5) % 6:04d}",
                                cpu="100m", spread=True, priority=0))
        for i in range(3):
            running.append(_pod(f"anti-{i}", "anti", node=f"n{2 * i + 1:04d}",
                                cpu="100m", anti=True, priority=0))
    # a loose pod of a bound template, in the same batch
    running.insert(5, _pod("low-loose", "low", cpu="600m", priority=0))
    app = ResourceTypes(pods=[
        _pod(f"hi-{i}", "hi", cpu="1200m", priority=1000) for i in range(5)
    ])

    def run(engine):
        wl.reset_name_counter()
        return simulate(
            ResourceTypes(nodes=copy.deepcopy(nodes), pods=copy.deepcopy(running)),
            [AppResource("a", copy.deepcopy(app))],
            engine=engine,
        )

    serial = run("oracle")
    monkeypatch.setattr(core_mod, "MIN_SCAN_RUN", 1)
    c0 = COUNTERS.get("expand_bound_clones_total")
    tpu = run("tpu")
    templates = 4 if terms else 2
    bound = len(running) - 1
    assert COUNTERS.get("expand_bound_clones_total") - c0 == bound - templates
    assert serial.preemptions
    assert _outcome(tpu) == _outcome(serial)


def test_bound_clone_counter_and_metrics_line():
    raw = [_pod(f"web-{i}", "web", node=f"n{i:04d}") for i in range(7)] + [
        _pod(f"db-{i}", "db", node=f"n{i:04d}", cpu="300m") for i in range(3)
    ] + [_pod(f"web-new-{i}", "web") for i in range(4)]
    c0 = COUNTERS.get("expand_bound_clones_total")
    _, index = _expand(raw)
    assert len(index.firsts) == 3
    # ten bound pods of two templates: two firsts, eight clones; the
    # loose clones are not counted
    assert COUNTERS.get("expand_bound_clones_total") - c0 == 8
    from open_simulator_tpu.serve.server import _observatory_lines

    lines = _observatory_lines(COUNTERS.snapshot())
    assert (
        f"simon_expand_bound_clones_total {COUNTERS.get('expand_bound_clones_total')}"
        in lines
    )


def test_index_and_default_groups_encode_alike():
    """The expansion index and the one-group-per-pod default encode the
    same batch: the same pins, the same class of every pod with the
    same class content, and the same placements from the scan."""
    from open_simulator_tpu.scheduler.engine import TpuEngine

    nodes = [_node(i) for i in range(6)]
    raw = [
        _pod("web-0", "web", node="n0003"),
        _pod("db-0", "db", cpu="300m", anti=True),
        _pod("web-1", "web"),
        _pod("web-2", "web", node="n0001"),
        _pod("db-1", "db", cpu="300m", anti=True),
        _pod("spread-0", "spread", spread=True),
        _pod("web-3", "web"),
        _pod("spread-1", "spread", spread=True),
    ]
    pods, index = _expand(raw)
    assert len(index.firsts) < len(pods)

    def encode(groups):
        oracle = Oracle(copy.deepcopy(nodes))
        batch = encode_batch(oracle, encode_cluster(oracle), pods, groups=groups)
        eng = TpuEngine(oracle)
        eng.begin_batch(pods, groups=groups)
        return batch, eng.scan_active(np.ones(len(pods), dtype=bool))

    grouped, placed_g = encode(index.groups())
    default, placed_d = encode(None)
    assert grouped.pinned_node.tolist() == default.pinned_node.tolist()
    assert grouped.class_of_pod.tolist() == default.class_of_pod.tolist()
    assert grouped.u == default.u
    for field in ("req_mcpu", "req_mem", "static_feasible", "want_ports"):
        assert np.array_equal(getattr(grouped, field), getattr(default, field)), field
    assert placed_g.tolist() == placed_d.tolist()
