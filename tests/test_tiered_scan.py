"""Tiered priority scanning + batched host replay (scheduler/core.py
_schedule_pods_priority, oracle.commit_simple_bulk, engine.begin_batch/
scan_active).

The contract under test: the tiered engine's vectorized escape checks
and bulk commits are EXACT reductions of the per-pod serial cycle —
placements, unscheduled reasons, preemptions, and the oracle's
post-batch state (per-node accounting, commit sequence, ports) must be
bit-identical to the serial oracle, and the per-phase trace notes must
name the sort/encode/scan/replay split the bench quotes.
"""

from __future__ import annotations

import numpy as np

from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.scheduler.core import AppResource, simulate
from open_simulator_tpu.testing import (
    make_fake_node,
    make_fake_pod,
    with_labels,
    with_priority,
)


def _cluster(nodes, pods=(), priority_classes=(), pdbs=()):
    c = ResourceTypes()
    c.nodes = list(nodes)
    c.pods = list(pods)
    c.priority_classes = list(priority_classes)
    c.pod_disruption_budgets = list(pdbs)
    return c


# a PodDisruptionBudget over the stress case's victims: they are out of
# the device dry run's scope (ops/preempt.py), so every preemptor takes
# the serial escape these tests are about
VICTIM_PDB = {
    "kind": "PodDisruptionBudget",
    "metadata": {"name": "victims", "namespace": "default"},
    "spec": {"selector": {"matchLabels": {"role": "victim"}}},
}


def _app(name, pods):
    r = ResourceTypes()
    r.pods = list(pods)
    return AppResource(name, r)


def _placement(result):
    out = {}
    for ns in result.node_status:
        for pod in ns.pods:
            out[pod["metadata"]["name"]] = ns.node["metadata"]["name"]
    return out


def _summary(res):
    return (
        _placement(res),
        sorted(u.pod["metadata"]["name"] for u in res.unscheduled_pods),
        sorted(ev.victim["metadata"]["name"] for ev in res.preemptions),
    )


def _tier_stress_case(n_nodes=6, n_extra_pre=3, n_zero=8):
    """Packed cluster + more preempting TIERS than the (monkeypatched)
    escape cap: every preemptor fails the scan and passes the
    PostFilter gates at its own distinct priority."""
    nodes = [make_fake_node(f"node-{i}", "1", "4Gi") for i in range(n_nodes)]
    victims = []
    for i in range(n_nodes):
        v = make_fake_pod(f"victim-{i}", "default", "800m", "1Gi", with_priority(0),
                          with_labels({"role": "victim"}))
        v["spec"]["nodeName"] = f"node-{i}"
        victims.append(v)
    pres = [
        make_fake_pod(f"pre-{i}", "default", "800m", "1Gi", with_priority(1000 - i))
        for i in range(n_extra_pre)
    ]
    zeros = [
        make_fake_pod(f"zero-{i}", "default", "50m", "8Mi", with_priority(0))
        for i in range(n_zero)
    ]
    return nodes, victims, pres, zeros


def test_tier_stress_across_escape_cap_matches_serial_oracle(monkeypatch):
    """Escape-heavy tier stress straddling MAX_SCAN_ESCAPES: distinct
    priorities (one tier each) force one escape per preemptor until the
    cap trips and the serial tail takes over — placements, reasons,
    and preemptions bit-identical to the serial oracle on both sides
    of the boundary."""
    from open_simulator_tpu.scheduler import core as core_mod
    from open_simulator_tpu.utils.trace import GLOBAL

    monkeypatch.setattr(core_mod, "MAX_SCAN_ESCAPES", 2)
    monkeypatch.setattr(core_mod, "MIN_SCAN_RUN", 4)

    def build():
        nodes, victims, pres, zeros = _tier_stress_case()
        return (
            _cluster(nodes, pods=[dict(v, spec=dict(v["spec"])) for v in victims],
                     pdbs=[VICTIM_PDB]),
            [_app("a", pres + zeros)],
        )

    cluster, apps = build()
    serial = simulate(cluster, apps, engine="oracle")
    cluster, apps = build()
    GLOBAL.reset()
    tpu = simulate(cluster, apps, engine="tpu")
    assert GLOBAL.notes.get("engine") == "priority-scan"
    assert GLOBAL.notes.get("priority-scan-escapes") == 2  # the cap
    assert GLOBAL.notes.get("priority-scan-serial-tail")
    # 3 preempting tiers + the zero tier, all distinct
    assert GLOBAL.notes.get("priority-scan-tiers") == 4
    assert _summary(serial) == _summary(tpu)
    assert len(tpu.preemptions) == 3  # every preemptor displaced a victim


def test_tier_stress_below_cap_matches_serial_oracle(monkeypatch):
    """Same scenario with the cap ABOVE the escape count: every
    preemptor escapes individually (one masked re-dispatch per round,
    no serial tail) and the result still matches serial exactly."""
    from open_simulator_tpu.scheduler import core as core_mod
    from open_simulator_tpu.utils.trace import GLOBAL

    monkeypatch.setattr(core_mod, "MIN_SCAN_RUN", 4)

    def build():
        nodes, victims, pres, zeros = _tier_stress_case()
        return (
            _cluster(nodes, pods=[dict(v, spec=dict(v["spec"])) for v in victims],
                     pdbs=[VICTIM_PDB]),
            [_app("a", pres + zeros)],
        )

    cluster, apps = build()
    serial = simulate(cluster, apps, engine="oracle")
    cluster, apps = build()
    GLOBAL.reset()
    tpu = simulate(cluster, apps, engine="tpu")
    assert GLOBAL.notes.get("priority-scan-escapes") == 3
    assert GLOBAL.notes.get("priority-scan-rounds") == 4
    assert GLOBAL.notes.get("priority-scan-serial-tail") is None
    assert _summary(serial) == _summary(tpu)


def test_priority_path_records_phase_notes(monkeypatch):
    """The per-phase trace split the bench quotes: sort / encode /
    scan / replay (plus expansion) all record wall-clock on the
    priority path."""
    from open_simulator_tpu.scheduler import core as core_mod
    from open_simulator_tpu.utils.trace import GLOBAL

    monkeypatch.setattr(core_mod, "MIN_SCAN_RUN", 4)
    nodes = [make_fake_node(f"node-{i}", "4", "16Gi") for i in range(3)]
    pods = [
        make_fake_pod(f"p-{i:02d}", "default", "200m", "256Mi",
                      with_priority(100 - i))
        for i in range(12)
    ]
    GLOBAL.reset()
    simulate(_cluster(nodes), [_app("a", pods)], engine="tpu")
    assert GLOBAL.notes.get("engine") == "priority-scan"
    assert GLOBAL.notes.get("priority-scan-tiers") == 12
    for name in (
        "host/expand", "priority/sort", "engine/encode", "engine/scan",
        "engine/replay",
    ):
        assert name in GLOBAL.phases, f"missing phase {name}"
        assert GLOBAL.phases[name].seconds >= 0


def test_bulk_replay_state_matches_serial_oracle(monkeypatch):
    """The batched host replay must leave the oracle in EXACTLY the
    serial state: per-node accounting (ceil + floor + nonzero), host
    ports, scalar resources, commit order (the MoreImportantPod
    start-time proxy), and per-node pod lists — exercised with a
    priority mix so _min_prio/saw_priority bookkeeping is covered."""
    from open_simulator_tpu.scheduler import core as core_mod

    monkeypatch.setattr(core_mod, "MIN_SCAN_RUN", 4)
    nodes = [make_fake_node(f"node-{i}", "8", "32Gi") for i in range(4)]
    for node in nodes:
        node["status"]["allocatable"]["example.com/accel"] = "8"
    pods = []
    for i in range(24):
        opts = [with_priority([-3, 0, 7, 400][i % 4])]
        if i % 6 == 0:
            opts.append(with_labels({"kind": "port"}))
        p = make_fake_pod(f"p-{i:02d}", "default", "300m", "256Mi", *opts)
        if i % 6 == 0:
            p["spec"]["containers"][0]["ports"] = [
                {"containerPort": 9000 + i, "hostPort": 9000 + i,
                 "protocol": "TCP"}
            ]
        if i % 5 == 0:
            p["spec"]["containers"][0]["resources"]["requests"][
                "example.com/accel"
            ] = "2"
        pods.append(p)

    def run(engine):
        from open_simulator_tpu.scheduler.core import Simulator

        sim = Simulator(engine=engine)
        sim.run_cluster(_cluster(nodes))
        sim.schedule_app(_app("a", pods))
        return sim.oracle

    o_serial = run("oracle")
    o_tpu = run("tpu")
    assert o_tpu._min_prio == o_serial._min_prio
    assert o_tpu.saw_priority == o_serial.saw_priority
    assert o_tpu._seq_counter == o_serial._seq_counter
    for ns_s, ns_t in zip(o_serial.nodes, o_tpu.nodes):
        assert [p["metadata"]["name"] for p in ns_t.pods] == [
            p["metadata"]["name"] for p in ns_s.pods
        ]
        for field in ("req_mcpu", "req_mem", "req_eph", "req_floor_mcpu",
                      "req_floor_mem", "nz_mcpu", "nz_mem"):
            assert getattr(ns_t, field) == getattr(ns_s, field), field
        assert ns_t.used_ports == ns_s.used_ports
        assert dict(ns_t.req_scalar) == dict(ns_s.req_scalar)
        for p in ns_t.pods:
            assert p["spec"]["nodeName"] == ns_t.name
            assert p["status"]["phase"] == "Running"
    # commit order identical pod-for-pod
    seq_s = sorted(o_serial.commit_seq.items(), key=lambda kv: kv[1])
    seq_t = sorted(o_tpu.commit_seq.items(), key=lambda kv: kv[1])
    assert [k for k, _ in seq_s] == [k for k, _ in seq_t]


def test_commit_simple_bulk_equals_per_pod_commits():
    """Unit equivalence: oracle.commit_simple_bulk vs the per-pod
    commit_simple walk on identical inputs."""
    from open_simulator_tpu.models import requests as req
    from open_simulator_tpu.scheduler.oracle import Oracle, _pod_host_ports

    def build():
        return Oracle([make_fake_node(f"n{i}", "8", "16Gi") for i in range(3)])

    pods_a = [
        make_fake_pod(f"p{i}", "default", "250m", "128Mi") for i in range(9)
    ]
    pods_b = [
        make_fake_pod(f"p{i}", "default", "250m", "128Mi") for i in range(9)
    ]
    node_idx = np.array([0, 1, 2, 0, 0, 1, 2, 2, 1])
    prios = np.array([0, 5, -2, 0, 0, 5, -2, 0, 9], dtype=np.int64)

    o1 = build()
    s = req.pod_request_summary(pods_a[0])
    for j, pod in enumerate(pods_a):
        o1._min_prio = min(o1._min_prio, int(prios[j]))
        o1.commit_simple(pod, o1.nodes[int(node_idx[j])], s,
                         tuple(_pod_host_ports(pod)))
    o2 = build()
    field_tbl = np.array(
        [[s.mcpu, s.mem, s.eph, s.floor_mcpu, s.floor_mem, s.nz_mcpu, s.nz_mem]],
        dtype=np.int64,
    )
    o2.commit_simple_bulk(
        pods_b, node_idx, np.zeros(9, dtype=np.int64), field_tbl,
        [()], [()], prios=prios,
    )
    assert o2._min_prio == min(int(prios.min()), o1._min_prio)
    assert o2._seq_counter == o1._seq_counter
    for n1, n2 in zip(o1.nodes, o2.nodes):
        assert [p["metadata"]["name"] for p in n1.pods] == [
            p["metadata"]["name"] for p in n2.pods
        ]
        assert (n1.req_mcpu, n1.req_mem, n1.nz_mcpu, n1.req_floor_mcpu) == (
            n2.req_mcpu, n2.req_mem, n2.nz_mcpu, n2.req_floor_mcpu
        )


def test_expand_index_groups_are_content_identical():
    """ExpandIndex invariant the whole tiered path rests on: group
    members match their group's first on everything but
    metadata.name and the node a bound pod names (whether it is bound
    is group content)."""
    import copy
    import json

    from open_simulator_tpu.models import workloads as wl

    res = ResourceTypes()
    raws = []
    for i in range(12):
        p = make_fake_pod(f"raw-{i}", "default", "100m", "64Mi")
        p = copy.deepcopy(p)
        p["spec"]["containers"][0]["image"] = "img"
        if i % 3 == 0:
            p["spec"]["priority"] = 1000
        if i % 4 == 1:
            p["spec"]["nodeName"] = f"node-{i}"
        raws.append(p)
    res.pods = raws
    res.deployments = [
        {
            "kind": "Deployment",
            "metadata": {"name": "web", "namespace": "d", "labels": {}},
            "spec": {
                "replicas": 4,
                "template": {
                    "spec": {
                        "containers": [
                            {"name": "c", "image": "img",
                             "resources": {"requests": {"cpu": "1"}}}
                        ]
                    }
                },
            },
        }
    ]
    index = wl.ExpandIndex()
    pods = wl.generate_valid_pods_from_app("app", res, [], index=index)
    assert len(index.group_of) == len(pods)

    def content(pod):
        d = {k: v for k, v in pod.items() if k != "metadata"}
        d["spec"] = {k: v for k, v in pod["spec"].items() if k != "nodeName"}
        d["bound"] = bool(pod["spec"].get("nodeName"))
        m = {k: v for k, v in (pod.get("metadata") or {}).items() if k != "name"}
        return json.dumps({"m": m, "rest": d}, sort_keys=True, default=str)

    # bound and loose pods of each priority: four bare-pod groups
    assert len(index.firsts) == 5
    for pod, gid in zip(pods, index.group_of):
        assert content(pod) == content(index.firsts[gid])
        # app-name label stamped through the shared labels dict
        assert pod["metadata"]["labels"][wl.LABEL_APP_NAME] == "app"


def test_pod_intern_key_memo_survives_reexpansion():
    """The raw-pod intern-key memo: a second expansion over the same
    raw dicts reuses the cached json keys (same group structure, fresh
    clone objects)."""
    from open_simulator_tpu.models import workloads as wl

    res = ResourceTypes()
    res.pods = [make_fake_pod(f"p-{i}", "default", "100m", "64Mi") for i in range(6)]
    i1 = wl.ExpandIndex()
    pods1 = wl.pods_excluding_daemon_sets(res, index=i1)
    i2 = wl.ExpandIndex()
    pods2 = wl.pods_excluding_daemon_sets(res, index=i2)
    assert i1.group_of == i2.group_of
    assert [p["metadata"]["name"] for p in pods1] == [
        p["metadata"]["name"] for p in pods2
    ]
    # fresh objects each run (no aliasing of returned pods)
    assert all(a is not b for a, b in zip(pods1, pods2))


def test_tiered_dense_distinct_priorities_still_single_scan(monkeypatch):
    """Dense distinct priorities (every pod its own tier) place in ONE
    dispatch with zero escapes when the cluster fits — the cliff
    scenario the tiered engine exists for."""
    from open_simulator_tpu.scheduler import core as core_mod
    from open_simulator_tpu.utils.trace import GLOBAL

    monkeypatch.setattr(core_mod, "MIN_SCAN_RUN", 4)
    nodes = [make_fake_node(f"node-{i}", "16", "64Gi") for i in range(4)]
    pods = [
        make_fake_pod(f"p-{i:03d}", "default", "100m", "64Mi",
                      with_priority(5000 - i))
        for i in range(48)
    ]
    serial = simulate(_cluster(nodes), [_app("a", pods)], engine="oracle")
    GLOBAL.reset()
    tpu = simulate(_cluster(nodes), [_app("a", pods)], engine="tpu")
    assert GLOBAL.notes.get("priority-scan-rounds") == 1
    assert GLOBAL.notes.get("priority-scan-escapes") == 0
    assert GLOBAL.notes.get("priority-scan-tiers") == 48
    assert not tpu.unscheduled_pods
    assert _placement(serial) == _placement(tpu)
