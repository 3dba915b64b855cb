"""DefaultPreemption's dry run on the device (ops/preempt.py) against
the serial oracle.

The priority path runs the dry run inside the scan for every armed pod
that fails, in scope; the replay applies the evictions at the pod's
step. Placements, PreemptionEvents (victim, node, preemptor, order) and
failure messages must equal `engine="oracle"` on every input, and a
pod out of the device's scope must take the serial escape.
"""

from __future__ import annotations

import numpy as np
import pytest

from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.scheduler import core as core_mod
from open_simulator_tpu.scheduler.core import AppResource, simulate
from open_simulator_tpu.testing import (
    make_fake_node,
    make_fake_pod,
    with_node_taints,
    with_preemption_policy,
    with_priority,
)
from open_simulator_tpu.utils.trace import COUNTERS, GLOBAL


def _cluster(nodes, pods=(), pdbs=()):
    return ResourceTypes(
        nodes=list(nodes), pods=list(pods), pod_disruption_budgets=list(pdbs)
    )


def _app(name, pods):
    return AppResource(name=name, resource=ResourceTypes(pods=list(pods)))


def _bound(name, node, cpu, prio):
    pod = make_fake_pod(name, "default", cpu, "256Mi", with_priority(prio))
    pod["spec"]["containers"][0]["image"] = "image-bound"
    pod["spec"]["nodeName"] = node
    return pod


def _outcome(res):
    """Everything that must match: placements per node in pod order,
    events in order, and each failed pod's message."""
    return (
        {
            st.node["metadata"]["name"]: [p["metadata"]["name"] for p in st.pods]
            for st in res.node_status
        },
        [(ev.victim["metadata"]["name"], ev.node_name, ev.preemptor)
         for ev in res.preemptions],
        sorted((u.pod["metadata"]["name"], u.reason) for u in res.unscheduled_pods),
    )


def _both(build, monkeypatch, min_run=3):
    """(oracle result, tpu result, the tpu run's counter deltas)."""
    serial = simulate(*build(), engine="oracle")
    monkeypatch.setattr(core_mod, "MIN_SCAN_RUN", min_run)
    names = ("preempt_device_total", "preempt_serial_escapes_total",
             "preempt_victims_total", "jax_dispatches_scan")
    before = {k: COUNTERS.get(k) for k in names}
    GLOBAL.reset()
    tpu = simulate(*build(), engine="tpu")
    delta = {k: COUNTERS.get(k) - before[k] for k in names}
    return serial, tpu, delta


def _random_case(seed):
    """Nodes of 1-2 cpu holding bound pods over three tiers, then app
    pods over four tiers (some preemptionPolicy Never): preemption
    chains, victims of victims, and pods nothing can help."""
    rng = np.random.RandomState(seed)
    n_nodes = int(rng.randint(4, 9))
    nodes = [
        make_fake_node(f"node-{i}", str(int(rng.choice([1, 2]))), "8Gi")
        for i in range(n_nodes)
    ]
    bound = []
    for i in range(int(rng.randint(2 * n_nodes, 4 * n_nodes))):
        bound.append(_bound(
            f"bound-{i:02d}", f"node-{int(rng.randint(0, n_nodes))}",
            f"{int(rng.choice([200, 300, 500]))}m", int(rng.choice([-5, 0, 5])),
        ))
    pods = []
    for i in range(int(rng.randint(8, 20))):
        opts = [with_priority(int(rng.choice([0, 10, 100, 1000])))]
        if rng.rand() < 0.15:
            opts.append(with_preemption_policy("Never"))
        pods.append(make_fake_pod(
            f"p-{i:02d}", "default", f"{int(rng.choice([300, 600, 900]))}m",
            "256Mi", *opts,
        ))
    return nodes, bound, pods


@pytest.mark.parametrize("seed", range(10))
def test_random_tiers_match_oracle_on_the_device(seed, monkeypatch):
    def build():
        nodes, bound, pods = _random_case(seed)
        return _cluster(nodes, bound), [_app("a", pods)]

    # every segment through the engine, the victims' own included
    serial, tpu, delta = _both(build, monkeypatch, min_run=1)
    assert _outcome(tpu) == _outcome(serial)
    assert delta["preempt_serial_escapes_total"] == 0
    events = _outcome(serial)[1]
    preemptions = sum(
        1 for i, ev in enumerate(events) if i == 0 or ev[2] != events[i - 1][2]
    )
    assert delta["preempt_device_total"] == preemptions
    assert delta["preempt_victims_total"] == len(events)


def test_random_cases_do_preempt():
    # the seeds above exercise the path: most of them preempt
    preempting = 0
    for seed in range(10):
        nodes, bound, pods = _random_case(seed)
        res = simulate(_cluster(nodes, bound), [_app("a", pods)], engine="oracle")
        preempting += bool(res.preemptions)
    assert preempting >= 7


def test_start_order_decides_ties(monkeypatch):
    # every node ties on criteria 1-4; rule 5 takes the node whose
    # earliest top-priority victim started LATEST (commit order), and
    # the reprieve keeps the earliest pod of each node
    def build():
        nodes = [make_fake_node(f"node-{i}", "2", "8Gi") for i in range(3)]
        order = [1, 2, 0, 1, 2, 0]
        bound = [_bound(f"low-{k}", f"node-{n}", "900m", 0) for k, n in enumerate(order)]
        pres = [make_fake_pod(f"pre-{i}", "default", "1000m", "256Mi",
                              with_priority(10)) for i in range(3)]
        return _cluster(nodes, bound), [_app("a", pres)]

    serial, tpu, delta = _both(build, monkeypatch)
    assert _outcome(tpu) == _outcome(serial)
    events = _outcome(tpu)[1]
    # node-0's later pod is low-5: the latest of the three victims
    assert [e[1] for e in events] == ["node-0", "node-2", "node-1"]
    assert [e[0] for e in events] == ["low-5", "low-4", "low-3"]
    assert delta["preempt_device_total"] == 3


def test_nodes_where_preemption_cannot_help(monkeypatch):
    # node-0 is tainted (unresolvable), node-1 holds a higher-priority
    # pod that nothing may evict, node-2 is the only node that helps;
    # the second preemptor finds no node at all and fails with the
    # oracle's message
    def build():
        nodes = [
            make_fake_node("node-0", "1", "8Gi", with_node_taints(
                [{"key": "k", "value": "v", "effect": "NoSchedule"}])),
            make_fake_node("node-1", "1", "8Gi"),
            make_fake_node("node-2", "1", "8Gi"),
        ]
        bound = [
            _bound("low-0", "node-0", "800m", 0),
            _bound("mid-1", "node-1", "800m", 500),
            _bound("low-2", "node-2", "800m", 0),
        ]
        pres = [make_fake_pod(f"pre-{i}", "default", "800m", "256Mi",
                              with_priority(100)) for i in range(2)]
        return _cluster(nodes, bound), [_app("a", pres)]

    serial, tpu, delta = _both(build, monkeypatch)
    assert _outcome(tpu) == _outcome(serial)
    assert _outcome(tpu)[1] == [("low-2", "node-2", "pre-0")]
    failed = dict(_outcome(tpu)[2])
    assert "pre-1" in failed and "low-2" in failed
    assert delta["preempt_serial_escapes_total"] == 0


def test_never_policy_fails_without_preempting(monkeypatch):
    def build():
        nodes = [make_fake_node(f"node-{i}", "1", "8Gi") for i in range(2)]
        bound = [_bound(f"low-{i}", f"node-{i}", "800m", 0) for i in range(2)]
        pods = [
            make_fake_pod("polite", "default", "800m", "256Mi", with_priority(300),
                          with_preemption_policy("Never")),
            make_fake_pod("rude", "default", "800m", "256Mi", with_priority(200)),
        ]
        return _cluster(nodes, bound), [_app("a", pods)]

    serial, tpu, delta = _both(build, monkeypatch, min_run=2)
    assert _outcome(tpu) == _outcome(serial)
    assert [e[2] for e in _outcome(tpu)[1]] == ["rude"]
    assert "polite" in dict(_outcome(tpu)[2])
    assert delta["preempt_device_total"] == 1


def test_pdb_matched_victim_takes_the_serial_escape(monkeypatch):
    # a PodDisruptionBudget selects a potential victim: out of the
    # device's scope, so that preemptor escapes to the serial cycle,
    # and the outcome still equals the oracle's
    # (victim-0, prio 50, is selected; pre-a may evict it and escapes,
    # pre-b may not, so its only candidate is in scope)
    def build():
        nodes = [make_fake_node(f"node-{i}", "1", "8Gi") for i in range(3)]
        bound = []
        for i in range(3):
            v = _bound(f"victim-{i}", f"node-{i}", "800m", 50 if i == 0 else 0)
            if i == 0:
                v["metadata"]["labels"] = {"app": "web"}
            bound.append(v)
        pdb = {
            "kind": "PodDisruptionBudget",
            "metadata": {"name": "web", "namespace": "default"},
            "spec": {"selector": {"matchLabels": {"app": "web"}}},
        }
        pres = [
            make_fake_pod("pre-a", "default", "800m", "256Mi", with_priority(100)),
            make_fake_pod("pre-b", "default", "800m", "256Mi", with_priority(20)),
        ]
        return _cluster(nodes, bound, [pdb]), [_app("a", pres)]

    serial, tpu, delta = _both(build, monkeypatch, min_run=2)
    assert _outcome(tpu) == _outcome(serial)
    assert [e[1:] for e in _outcome(tpu)[1]] == [("node-2", "pre-a"), ("node-1", "pre-b")]
    assert delta["preempt_serial_escapes_total"] == 1
    assert delta["preempt_device_total"] == 1
    assert GLOBAL.notes.get("engine") == "priority-scan"


@pytest.mark.parametrize("labels", [{"app": "web"}, {"app": "other"}])
def test_pdb_escape_only_where_a_matched_pod_is_at_stake(labels, monkeypatch):
    # the PDB selects app=web: a cluster whose victims carry other
    # labels stays on the device
    def build():
        nodes = [make_fake_node(f"node-{i}", "1", "8Gi") for i in range(2)]
        bound = [_bound(f"victim-{i}", f"node-{i}", "800m", 0) for i in range(2)]
        for v in bound:
            v["metadata"]["labels"] = dict(labels)
        pdb = {
            "kind": "PodDisruptionBudget",
            "metadata": {"name": "web", "namespace": "default"},
            "spec": {"selector": {"matchLabels": {"app": "web"}}},
        }
        pres = [make_fake_pod(f"pre-{i}", "default", "800m", "256Mi",
                              with_priority(100)) for i in range(2)]
        return _cluster(nodes, bound, [pdb]), [_app("a", pres)]

    serial, tpu, delta = _both(build, monkeypatch, min_run=2)
    assert _outcome(tpu) == _outcome(serial)
    matched = labels["app"] == "web"
    assert (delta["preempt_serial_escapes_total"] > 0) is matched
    assert (delta["preempt_device_total"] > 0) is not matched


@pytest.mark.parametrize("n_pre", [2, 6])
def test_dispatches_do_not_grow_with_preemptions(n_pre, monkeypatch):
    # the cluster batch, the app batch and the deferred victims' batch:
    # three scans whatever the number of preemptions
    def build():
        nodes = [make_fake_node(f"node-{i}", "1", "8Gi") for i in range(6)]
        bound = [_bound(f"low-{i}", f"node-{i}", "800m", 0) for i in range(6)]
        pres = [make_fake_pod(f"pre-{i}", "default", "800m", "256Mi",
                              with_priority(100)) for i in range(n_pre)]
        return _cluster(nodes, bound), [_app("a", pres)]

    serial, tpu, delta = _both(build, monkeypatch, min_run=1)
    assert _outcome(tpu) == _outcome(serial)
    assert delta["preempt_device_total"] == n_pre
    assert delta["jax_dispatches_scan"] == 3


def test_failure_reasons_are_reused_within_a_run(monkeypatch):
    # victims fail back to back with nothing committed between them:
    # one reason computation for the run, identical messages
    def build():
        nodes = [make_fake_node(f"node-{i}", "1", "8Gi") for i in range(4)]
        bound = [_bound(f"low-{i}", f"node-{i}", "800m", 0) for i in range(4)]
        pres = [make_fake_pod(f"pre-{i}", "default", "800m", "256Mi",
                              with_priority(100)) for i in range(4)]
        return _cluster(nodes, bound), [_app("a", pres)]

    serial, tpu, _ = _both(build, monkeypatch, min_run=1)
    assert _outcome(tpu) == _outcome(serial)
    assert len(tpu.unscheduled_pods) == 4
    assert GLOBAL.phases["engine/failure-reasons"].count == 1
    assert "engine/preempt-replay" in GLOBAL.phases
    assert "engine/deferred" in GLOBAL.phases


def test_fused_kernel_refuses_preemption_by_name():
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.ops.scan import ScanFeatures

    feats = ScanFeatures(*([False] * 9), preempt=True)
    assert pallas_scan.build_plan(None, None, None, feats) is None
    assert "preemption" in pallas_scan.last_reject()


def test_table_overflow_escapes(monkeypatch):
    # with a table one slot short, the small pods' commits overflow it
    # and the big preemptor after them takes the serial escape; the
    # outcome still equals the oracle's
    from open_simulator_tpu.ops import preempt

    monkeypatch.setattr(
        preempt, "table_slots",
        lambda oracle, cluster, batch, n_pinned=0: max(len(ns.pods) for ns in oracle.nodes),
    )

    def build():
        nodes = [make_fake_node(f"node-{i}", "4", "8Gi") for i in range(2)]
        bound = [_bound(f"low-{i:02d}", f"node-{i % 2}", "300m", 0) for i in range(20)]
        pods = [make_fake_pod(f"small-{i}", "default", "100m", "64Mi",
                              with_priority(100)) for i in range(4)]
        pods.append(make_fake_pod("big", "default", "3500m", "256Mi", with_priority(50)))
        return _cluster(nodes, bound), [_app("a", pods)]

    serial, tpu, delta = _both(build, monkeypatch)
    assert _outcome(tpu) == _outcome(serial)
    assert [e[2] for e in _outcome(tpu)[1]][:1] == ["big"]
    assert delta["preempt_serial_escapes_total"] == 1
    assert delta["preempt_device_total"] == 0
