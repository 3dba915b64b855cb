"""A whole run, on the CPU at a small size, with the timed path broken
underneath: `correct` must come out false for each fault a cell can
have (an answer altered where it is produced, half of the pods left
out, the state returned unchanged). One chip, so no exchange between
chips to leave out."""

import json

import pytest

from benchmark import harness, run

CELLS = {
    "sigscale-5k.plan": ("open_simulator_tpu.apply.applier", "probe_plan", 0.01),
    "envelope-5k.spread": ("open_simulator_tpu.scheduler.core", "simulate", 0.05),
    "envelope-5k.anti": ("open_simulator_tpu.scheduler.core", "simulate", 0.05),
    "envelope-5k.basic": ("open_simulator_tpu.scheduler.core", "simulate", 0.02),
}


def _placed(node_status):
    """(node index, pod index) of every pod the request placed."""
    return [
        (i, j)
        for i, ns in enumerate(node_status)
        for j, p in enumerate(ns.pods)
        if (p.get("metadata") or {}).get("ownerReferences")
    ]


def break_result(result, fault: str):
    status = result.node_status
    placed = _placed(status)
    assert placed
    if fault == "altered":
        i, j = placed[0]
        pod = status[i].pods.pop(j)
        status[(i + 1) % len(status)].pods.append(pod)
    elif fault == "half":
        for i, j in sorted(placed[::2], reverse=True):
            status[i].pods.pop(j)
    elif fault == "unchanged":
        for i, j in sorted(placed, reverse=True):
            status[i].pods.pop(j)
    return result


def run_cell(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", "2147483659", "--seconds", "2",
                   "--trace", "0", "--scale", str(CELLS[cell][2])])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def no_chip_check(monkeypatch):
    monkeypatch.setattr(harness, "device",
                        lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", [None, "altered", "half", "unchanged"])
def test_fault_reads_incorrect(cell, fault, monkeypatch, capsys, no_chip_check):
    import importlib

    module, name, _ = CELLS[cell]
    mod = importlib.import_module(module)
    orig = getattr(mod, name)
    if fault is not None:
        if name == "probe_plan":
            def broken(*a, _orig=orig, **k):
                res = _orig(*a, **k)
                break_result(res.result, fault)
                return res
        else:
            def broken(*a, _orig=orig, **k):
                return break_result(_orig(*a, **k), fault)
        monkeypatch.setattr(mod, name, broken)
    out = run_cell(cell, capsys)
    assert out["correct"] is (fault is None), out["checks"]
