"""The preemption cell on the CPU at a small size: the program reads 0
on every number compared with reference_preempt, the control (the
reference in int32 / float32) fails one of them, and a whole run with
the timed path broken underneath reads `correct: false` for each fault
a preemption can have (a preemption on the wrong node, victims not
evicted, the preemptor dropped)."""

import json

import pytest

from benchmark import control, harness, run

CELL = "preempt-5k.basic"
SCALE = 0.02


def break_result(result, fault: str):
    status = {ns.node["metadata"]["name"]: ns for ns in result.node_status}
    events = result.preemptions
    assert events
    first = events[0].preemptor
    if fault == "wrong_node":
        others = [n for n in status if n != events[0].node_name]
        for ev in events:
            if ev.preemptor == first:
                ev.node_name = others[0]
    elif fault == "not_evicted":
        back = {id(ev.victim) for ev in events}
        for ev in events:
            status[ev.node_name].pods.append(ev.victim)
        result.unscheduled_pods[:] = [
            u for u in result.unscheduled_pods if id(u.pod) not in back
        ]
        events.clear()
    elif fault == "dropped":
        ns = status[events[0].node_name]
        ns.pods[:] = [p for p in ns.pods if p["metadata"]["name"] != first]
    return result


@pytest.fixture
def no_chip_check(monkeypatch):
    monkeypatch.setattr(harness, "device",
                        lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.mark.parametrize("fault", [None, "wrong_node", "not_evicted", "dropped"])
def test_fault_reads_incorrect(fault, monkeypatch, capsys, no_chip_check):
    from open_simulator_tpu.scheduler import core

    if fault is not None:
        orig = core.simulate
        monkeypatch.setattr(core, "simulate",
                            lambda *a, **k: break_result(orig(*a, **k), fault))
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2",
                   "--trace", "0", "--scale", str(SCALE)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is (fault is None), out["checks"]
    if fault == "wrong_node":
        assert out["checks"]["preemption_mismatch"]["value"] > 0


def test_program_matches_reference_and_control_fails():
    for seed, program, low in control.readings(CELL, [7, 3_000_000_019], 2.0, SCALE):
        assert all(v == 0 for v in program.values()), (seed, program)
        assert any(v > 0 for v in low.values()), (seed, low)
