"""DefaultPreemption's dry run inside the fused kernel
(pallas_scan._make_kernel's dry_run, interpret mode on the CPU) against
the serial oracle: the cases of tests/test_preempt_device.py, which run
the XLA scan's dry run, with the kernel forced on. Every test also
checks that its preemption rounds ran on the kernel and not the XLA
scan.
"""

from __future__ import annotations

import numpy as np
import pytest

from open_simulator_tpu.ops import pallas_scan
from open_simulator_tpu.testing import make_fake_node, make_fake_pod, with_priority

import test_preempt_device as dev


@pytest.fixture
def kernel(monkeypatch):
    """Force the fused kernel; count the scans it ran with a dry run."""
    monkeypatch.setattr(pallas_scan, "FORCE_ENABLE", True)
    runs = {"preempt": 0, "all": 0}
    decode = pallas_scan.decode_scan_output

    def counted(plan, out, p_total):
        runs["all"] += 1
        runs["preempt"] += plan.pre is not None
        return decode(plan, out, p_total)

    monkeypatch.setattr(pallas_scan, "decode_scan_output", counted)
    return runs


@pytest.mark.parametrize("seed", [2, 4, 7])
def test_random_tiers_match_oracle_in_the_kernel(seed, kernel, monkeypatch):
    # the XLA test's random clusters on 1-cpu nodes: few pods a node,
    # so the slots stay within the kernel's _PRE_MAX_K
    def build():
        nodes, bound, pods = dev._random_case(seed)
        for nd in nodes:
            for k in ("allocatable", "capacity"):
                nd["status"][k]["cpu"] = "1"
        return dev._cluster(nodes, bound), [dev._app("a", pods)]

    serial, tpu, delta = dev._both(build, monkeypatch, min_run=1)
    assert dev._outcome(tpu) == dev._outcome(serial)
    assert serial.preemptions
    assert delta["preempt_serial_escapes_total"] == 0
    assert kernel["preempt"] >= 1


@pytest.mark.parametrize("case", [
    "test_start_order_decides_ties",
    "test_nodes_where_preemption_cannot_help",
    "test_never_policy_fails_without_preempting",
    "test_pdb_matched_victim_takes_the_serial_escape",
    "test_table_overflow_escapes",
    "test_failure_reasons_are_reused_within_a_run",
])
def test_device_cases_in_the_kernel(case, kernel, monkeypatch):
    getattr(dev, case)(monkeypatch)
    assert kernel["preempt"] >= 1


@pytest.mark.parametrize("n_pre", [2, 6])
def test_kernel_runs_do_not_grow_with_preemptions(n_pre, kernel, monkeypatch):
    # the cluster batch, the app batch and the deferred victims' batch:
    # three kernel runs whatever the number of preemptions
    def build():
        nodes = [make_fake_node(f"node-{i}", "1", "8Gi") for i in range(6)]
        bound = [dev._bound(f"low-{i}", f"node-{i}", "800m", 0) for i in range(6)]
        pres = [make_fake_pod(f"pre-{i}", "default", "800m", "256Mi",
                              with_priority(100)) for i in range(n_pre)]
        return dev._cluster(nodes, bound), [dev._app("a", pres)]

    serial, tpu, delta = dev._both(build, monkeypatch, min_run=1)
    assert dev._outcome(tpu) == dev._outcome(serial)
    assert delta["preempt_device_total"] == n_pre
    assert kernel == {"preempt": 1, "all": 3}


def test_kernel_refuses_more_slots_than_it_unrolls():
    # more slots a node than the kernel's dry run holds: it refuses by
    # name and the XLA scan runs the dry run
    from open_simulator_tpu.ops.encode import (
        encode_batch,
        encode_cluster,
        encode_dynamic,
        features_of_batch,
    )
    from open_simulator_tpu.ops.preempt import _FIELDS
    from open_simulator_tpu.scheduler.oracle import Oracle

    nodes = [make_fake_node(f"node-{i}", "4", "8Gi") for i in range(3)]
    pods = [make_fake_pod("p", "default", "1", "1Gi", with_priority(5))]
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    k = pallas_scan._PRE_MAX_K + 8
    table = {f: np.zeros((k, 3), np.int64) for f in _FIELDS}
    table.update(valid=np.zeros((k, 3), bool), hard=np.zeros((k, 3), bool))
    feats = features_of_batch(cluster, batch)._replace(preempt=True)
    pre = (table, 1, np.array([5]), np.array([True]), np.array([False]))
    assert pallas_scan.build_plan(cluster, batch, dyn, feats, preempt=pre) is None
    assert "slots" in pallas_scan.last_reject()
