"""Per-phase tracing (utils/trace.py) — SURVEY.md §5: the reference has
no tracing; the TPU build records per-phase wall-clock."""

import json

from open_simulator_tpu.utils.trace import GLOBAL, Trace, phase


def test_phase_accumulates():
    tr = Trace()
    with phase("a", tr):
        pass
    with phase("a", tr):
        pass
    with phase("b", tr):
        pass
    d = tr.as_dict()
    assert [p["name"] for p in d["phases"]] == ["a", "b"]
    assert d["phases"][0]["count"] == 2
    assert d["total_seconds"] >= 0
    json.loads(tr.as_json())


def test_append_note_accumulates_and_caps_by_entry_count():
    tr = Trace()
    # values containing ';' must not eat into the 50-entry cap
    for i in range(60):
        tr.append_note("deg", f"event {i}: RESOURCE_EXHAUSTED; retrying")
    note = tr.notes["deg"]
    assert note.startswith("event 0:") and "event 49" in note
    assert note.endswith("; ...") and "event 50" not in note
    # note() overwrites and resets the accumulation
    tr.note("deg", "fresh")
    tr.append_note("deg", "after")
    assert tr.notes["deg"] == "after"
    tr.reset()
    assert tr.appended == {} and tr.notes == {}


def _web_app(replicas=3):
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.scheduler.core import AppResource
    from open_simulator_tpu.testing import make_fake_node

    cluster = ResourceTypes()
    cluster.nodes = [make_fake_node(f"n{i}", cpu="8", memory="16Gi") for i in range(3)]
    res = ResourceTypes()
    res.deployments = [
        {
            "kind": "Deployment",
            "metadata": {"name": "web", "namespace": "d"},
            "spec": {
                "replicas": replicas,
                "template": {
                    "spec": {
                        "containers": [
                            {
                                "name": "c",
                                "image": "img",
                                "resources": {"requests": {"cpu": "1"}},
                            }
                        ]
                    }
                },
            },
        }
    ]
    return cluster, [AppResource("web", res)]


def _phase_names():
    return {p["name"] for p in GLOBAL.as_dict()["phases"]}


def _host_event_names(log_dir):
    """Names of the host events in the one capture under `log_dir`."""
    from jax.profiler import ProfileData

    found = list(log_dir.rglob("*.xplane.pb"))
    assert len(found) == 1, found
    pd = ProfileData.from_file(str(found[0]))
    return {
        e.name
        for plane in pd.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }


def test_engine_records_phases():
    from open_simulator_tpu.scheduler.core import simulate

    GLOBAL.reset()
    cluster, apps = _web_app()
    out = simulate(cluster, apps, engine="tpu")
    assert not out.unscheduled_pods
    assert {"engine/encode", "engine/scan"} <= _phase_names()
    GLOBAL.reset()


def test_phase_annotates_profiler_capture(tmp_path):
    # a phase is a host annotation of a running capture, on the same
    # clock as the device ops: idle gaps can be put down to it
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with phase("x/y", Trace()):
            pass
    finally:
        jax.profiler.stop_trace()
    assert "x/y" in _host_event_names(tmp_path)


def test_trace_imports_without_jax():
    import subprocess
    import sys

    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from open_simulator_tpu.utils.trace import GLOBAL, phase\n"
        "with phase('a/b'): pass\n"
        "assert GLOBAL.phase_seconds('a/b') >= 0 and 'a/b' in GLOBAL.phases\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_simulate_records_cluster_and_encode_spans():
    from open_simulator_tpu.scheduler.core import simulate

    GLOBAL.reset()
    cluster, apps = _web_app()
    simulate(cluster, apps, engine="tpu")
    assert {
        "sim/copy", "sim/run-cluster", "sim/node-status",
        "engine/encode", "engine/encode-cluster", "engine/encode-batch",
        "engine/encode-state", "engine/kernel-plan",
    } <= _phase_names()
    GLOBAL.reset()


def test_probe_plan_records_lower_bound_and_finalize_spans():
    import gc

    from open_simulator_tpu.apply.applier import probe_plan
    from open_simulator_tpu.testing import make_fake_node

    assert gc.isenabled()
    GLOBAL.reset()
    cluster, apps = _web_app(replicas=30)
    res = probe_plan(cluster, apps, make_fake_node("tpl", cpu="8", memory="16Gi"), max_count=4)
    assert res.success and res.new_node_count == 1
    names = _phase_names()
    assert {
        "sweep/pad", "sweep/index", "sweep/kernel-plan", "sweep/lower-bound",
        "apply/clear-memos", "apply/gc",
    } <= names
    assert "apply/lower-bound" not in names
    assert gc.isenabled()
    GLOBAL.reset()


def test_fused_scan_counts_fetched_bytes(monkeypatch):
    # the fused kernel's one fetch is a device-to-host transfer like
    # the XLA scan's, and the kernel keeps its name in a trace
    from open_simulator_tpu.ops import pallas_scan as ps
    from open_simulator_tpu.scheduler.core import simulate
    from open_simulator_tpu.utils.trace import COUNTERS

    monkeypatch.setattr(ps, "FORCE_ENABLE", True)
    GLOBAL.reset()
    cluster, apps = _web_app()
    before = COUNTERS.get("device_transfer_d2h_bytes_total")
    out = simulate(cluster, apps, engine="tpu")
    assert not out.unscheduled_pods
    assert GLOBAL.notes["batch-kernel"].startswith("pallas")
    # placements (8 x 128 lanes) and six node-state rows, int32
    assert COUNTERS.get("device_transfer_d2h_bytes_total") - before >= 4 * 7 * 8 * 128
    assert {c.fn.__name__ for c in ps._COMPILED_CACHE.values()} == {"pod_scan_fused"}
    GLOBAL.reset()


def test_profile_dir_writes_one_capture_with_phases(tmp_path, monkeypatch):
    import os

    from open_simulator_tpu.cli import main

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.chdir(repo)
    monkeypatch.delenv("SIMON_PROFILE_DIR", raising=False)
    prof = tmp_path / "prof"
    code = main([
        "apply", "-f", "example/simon-config.yaml", "--format", "json",
        "--profile-dir", str(prof),
    ])
    assert code == 0
    assert "SIMON_PROFILE_DIR" not in os.environ
    names = _host_event_names(prof)
    assert {"sweep/lower-bound", "sweep/probe", "apply/replay", "apply/gc"} <= names


def test_kernel_fallback_names_reason():
    # an open-local batch is outside the fused kernel's scope: the
    # trace must say so instead of silently noting a fallback
    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.scheduler.core import AppResource, simulate
    from open_simulator_tpu.testing import make_fake_node, make_fake_pod
    from open_simulator_tpu.utils.trace import GLOBAL

    node = make_fake_node("s0", "8", "16Gi")
    node["metadata"].setdefault("annotations", {})[
        "simon/node-local-storage"
    ] = (
        '{"vgs": [{"name": "open-local-pool-0", "capacity": 107374182400}],'
        ' "devices": []}'
    )
    cluster = ResourceTypes()
    cluster.nodes = [node]
    pod = make_fake_pod("p", "default", "1", "1Gi")
    pod["metadata"]["annotations"] = {
        "simon/pod-local-storage": '{"volumes": [{"kind": "LVM", "size": 1073741824}]}'
    }
    GLOBAL.reset()
    res = simulate(cluster, [AppResource("a", ResourceTypes(pods=[pod]))], engine="tpu")
    assert not res.unscheduled_pods
    note = GLOBAL.notes.get("batch-kernel", "")
    assert note.startswith("xla-scan (")
    assert "storage" in note or "no TPU" in note


def test_rate_bucket_boundary_deterministic_fake_clock():
    """The window-bucket edge case (ISSUE 5 satellite): an event marked
    mid-bucket used to be included or dropped depending on the READ
    clock's sub-second phase (`now - bucket <= window`), so two reads
    of the same history around a boundary disagreed — double-counted in
    one window, missing from the next. Whole-bucket membership
    (`bucket > floor(now) - window`) gives one verdict per (event,
    read-second) pair regardless of fractional alignment."""
    from open_simulator_tpu.utils.trace import Counters

    t = [1000.0]
    c = Counters(clock=lambda: t[0])
    c.mark("x")  # bucket 1000
    t[0] = 1000.9
    c.mark("x")  # same bucket (mid-bucket event — the alignment trap)

    # exactly window-old: bucket 1000 is OUTSIDE the trailing 60 whole
    # buckets ending at floor(now)=1060, at EVERY sub-second phase
    # (the old test included it at now=1060.0 and dropped it at 1060.5)
    for frac in (0.0, 0.2, 0.5, 0.9):
        t[0] = 1060.0 + frac
        assert c.rate("x", 60.0) == 0.0, f"phase {frac}"

    # one bucket earlier it is INSIDE at every phase
    for frac in (0.0, 0.5, 0.99):
        t[0] = 1059.0 + frac
        assert c.rate("x", 60.0) > 0.0, f"phase {frac}"


def test_rate_young_stream_denominator_and_totals():
    from open_simulator_tpu.utils.trace import Counters

    t = [500.0]
    c = Counters(clock=lambda: t[0])
    for _ in range(10):
        c.mark("q")
    t[0] = 502.0
    # young stream: denominator is the observed age (2s), not the window
    assert c.rate("q", 60.0) == 10 / 2.0
    # old stream: full-window denominator
    t[0] = 500.0 + 120.0
    assert c.rate("q", 60.0) == 0.0  # all events aged out
    c.mark("q")  # bucket 620
    t[0] = 630.0
    assert c.rate("q", 60.0) == 1 / 60.0


def test_rate_whole_bucket_membership():
    """A bucket is in or out as a unit: the window is the `window_s`
    whole buckets ending at floor(now), so mid-bucket event times and
    mid-second read times cannot shift membership."""
    from open_simulator_tpu.utils.trace import Counters

    t = [100.0]
    c = Counters(clock=lambda: t[0])
    c.mark("e")          # bucket 100
    t[0] = 100.7
    c.mark("e")          # bucket 100 again
    t[0] = 101.0
    c.mark("e")          # bucket 101
    # floor(160.9)=160, cutoff=100: bucket 101 in, bucket 100 out —
    # BOTH of bucket 100's events leave together, including the one
    # marked at 100.7 that the old arithmetic would have kept
    t[0] = 160.9
    assert c.rate("e", 60.0) == 1 / 60.0
    # floor(161.4)=161, cutoff=101: bucket 101 ages out as a unit too
    t[0] = 161.4
    assert c.rate("e", 60.0) == 0.0
