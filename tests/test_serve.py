"""`simon serve` — the what-if scheduling daemon (serve/).

The load-bearing guarantees:

- COALESCING CONFORMANCE: B concurrent requests answered as scenario
  rows of one batched masked scan produce response bodies
  byte-identical to B standalone ``simulate()`` runs, and the device
  dispatch counter proves <= ceil(B / max_batch) dispatches for the
  burst.
- BACKPRESSURE: the bounded queue rejects at depth with 503 +
  Retry-After; a request whose deadline expires in the queue is shed
  with a machine-readable PARTIAL/503 body.
- LIFECYCLE: SIGTERM drains in-flight requests then exits 0; a drain
  that cannot finish within --drain-timeout sheds and exits 3.
"""

from __future__ import annotations

import copy
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from open_simulator_tpu.models.decode import ResourceTypes
from open_simulator_tpu.models.workloads import reset_name_counter
from open_simulator_tpu.runtime.budget import Budget
from open_simulator_tpu.scheduler.core import AppResource, simulate
from open_simulator_tpu.serve.coalescer import Coalescer, PendingRequest
from open_simulator_tpu.serve.server import ServeDaemon, parse_request_body
from open_simulator_tpu.serve.session import (
    Session,
    WhatIfRequest,
    result_payload,
)
from open_simulator_tpu.utils.trace import COUNTERS


def make_node(name, cpu, mem_gi):
    return {
        "kind": "Node",
        "metadata": {"name": name, "labels": {"kubernetes.io/hostname": name}},
        "status": {
            "allocatable": {
                "cpu": str(cpu),
                "memory": f"{mem_gi}Gi",
                "pods": "110",
            }
        },
    }


def deployment(name, replicas, cpu="500m", mem="1Gi", priority=None):
    spec = {
        "containers": [
            {
                "name": "c",
                "image": f"img-{name}",
                "resources": {"requests": {"cpu": cpu, "memory": mem}},
            }
        ]
    }
    if priority is not None:
        spec["priority"] = priority
    return {
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": "serve", "labels": {"app": name}},
        "spec": {"replicas": replicas, "template": {"spec": spec}},
    }


def build_cluster() -> ResourceTypes:
    """Small but featureful: a bound pod, a dangling pod (unknown
    nodeName), and a daemonset — the cluster-pod handling edge cases
    ride every scenario."""
    cluster = ResourceTypes()
    cluster.nodes = [make_node(f"serve-n-{i}", 8, 32) for i in range(4)]
    cluster.pods = [
        {
            "kind": "Pod",
            "metadata": {"name": "bound", "namespace": "d"},
            "spec": {
                "nodeName": "serve-n-1",
                "containers": [
                    {
                        "name": "c",
                        "image": "x",
                        "resources": {"requests": {"cpu": "1", "memory": "1Gi"}},
                    }
                ],
            },
        },
        {
            "kind": "Pod",
            "metadata": {"name": "dangle", "namespace": "d"},
            "spec": {
                "nodeName": "node-that-left",
                "containers": [
                    {
                        "name": "c",
                        "image": "x",
                        "resources": {"requests": {"cpu": "1", "memory": "1Gi"}},
                    }
                ],
            },
        },
    ]
    cluster.daemon_sets = [
        {
            "kind": "DaemonSet",
            "metadata": {"name": "ds", "namespace": "d"},
            "spec": {
                "template": {
                    "spec": {
                        "containers": [
                            {
                                "name": "c",
                                "image": "x",
                                "resources": {
                                    "requests": {"cpu": "100m", "memory": "128Mi"}
                                },
                            }
                        ]
                    }
                }
            },
        }
    ]
    return cluster


def request_of(name, replicas, **kw) -> WhatIfRequest:
    res = ResourceTypes()
    res.deployments = [deployment(name, replicas, **kw)]
    return WhatIfRequest(apps=[AppResource(name, res)])


def serial_body(cluster, req: WhatIfRequest) -> bytes:
    """The standalone-run answer the coalesced body must equal
    byte-for-byte: a fresh simulate() over deep copies with the name
    counter reset, exactly what a one-shot CLI run would compute."""
    reset_name_counter()
    result = simulate(
        copy.deepcopy(cluster),
        [AppResource(a.name, copy.deepcopy(a.resource)) for a in req.apps],
        engine="tpu",
    )
    return result_payload(result)


def wait_until(pred, timeout=60.0, interval=0.01):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(interval)
    return False


# -- coalescing conformance ------------------------------------------------


def test_coalesced_batch_byte_identical_to_serial_runs():
    cluster = build_cluster()
    session = Session(cluster)
    reqs = [
        request_of("alpha", 4),
        request_of("beta", 7, cpu="2", mem="4Gi"),
        request_of("gamma", 40, cpu="2"),  # overflows: failures + reasons
        request_of("delta", 1, cpu="250m", mem="256Mi"),
    ]
    d0 = COUNTERS.get("serve_device_dispatches_total")
    replies = session.evaluate_batch(reqs)
    # one coalesced tick of B batchable requests = ONE device dispatch
    assert COUNTERS.get("serve_device_dispatches_total") - d0 == 1
    for req, reply in zip(reqs, replies):
        assert reply.status == 200
        assert reply.meta["engine"] == "coalesced-scan"
        assert reply.body == serial_body(cluster, req)
    # the answers themselves are real: gamma reports failures
    gamma = json.loads(replies[2].body)
    assert not gamma["success"] and gamma["unscheduledPods"]


def test_repeated_batches_stay_pristine():
    """Replay must not pollute the session's shared cluster pod dicts:
    a second identical batch re-encodes them and any leaked nodeName
    would read as a pin (answers would drift batch over batch)."""
    cluster = build_cluster()
    session = Session(cluster)
    reqs = [request_of("alpha", 4), request_of("beta", 6)]
    first = session.evaluate_batch(reqs)
    second = session.evaluate_batch(reqs)
    assert [r.body for r in first] == [r.body for r in second]


def test_priority_request_routes_serial_with_identical_body():
    cluster = build_cluster()
    session = Session(cluster)
    reqs = [request_of("plain", 3), request_of("crit", 2, priority=100000)]
    replies = session.evaluate_batch(reqs)
    assert replies[0].meta["engine"] == "coalesced-scan"
    assert replies[1].meta["engine"] == "serial"
    for req, reply in zip(reqs, replies):
        assert reply.body == serial_body(cluster, req)


def test_burst_dispatch_bound_ceil_b_over_chunk():
    """B requests enqueued while the dispatcher is held must coalesce
    into ceil(B / max_batch) ticks, each tick one device dispatch —
    the counters prove the micro-batching actually happened."""
    cluster = build_cluster()
    session = Session(cluster)
    coal = Coalescer(session, max_batch=2, queue_depth=16)
    coal.hold = threading.Event()  # dispatcher parks until released
    coal.start()
    reqs = [request_of(f"burst-{i}", 3 + i) for i in range(5)]
    pendings = [PendingRequest(request=r, budget=Budget(None)) for r in reqs]
    d0 = COUNTERS.get("serve_device_dispatches_total")
    b0 = COUNTERS.get("serve_batches_total")
    for p in pendings:
        assert coal.submit(p)
    coal.hold.set()
    for p in pendings:
        assert p.done.wait(timeout=120), "request never answered"
    assert COUNTERS.get("serve_batches_total") - b0 == 3  # ceil(5/2)
    assert COUNTERS.get("serve_device_dispatches_total") - d0 <= 3
    for req, p in zip(reqs, pendings):
        assert p.reply.status == 200
        assert p.reply.body == serial_body(cluster, req)
    coal.close()


# -- backpressure ----------------------------------------------------------


def test_queue_expired_deadline_sheds_with_partial_body():
    cluster = build_cluster()
    session = Session(cluster)
    coal = Coalescer(session, max_batch=4, queue_depth=16)
    coal.hold = threading.Event()
    coal.start()
    doomed = PendingRequest(
        request=request_of("doomed", 1), budget=Budget(0.01)
    )
    fine = PendingRequest(request=request_of("fine", 1), budget=Budget(None))
    s0 = COUNTERS.get("serve_shed_deadline_total")
    assert coal.submit(doomed) and coal.submit(fine)
    time.sleep(0.05)  # let the deadline expire in the queue
    coal.hold.set()
    assert doomed.done.wait(timeout=120) and fine.done.wait(timeout=120)
    assert doomed.reply.status == 503
    body = json.loads(doomed.reply.body)
    assert body["partial"] is True and body["reason"] == "deadline"
    assert COUNTERS.get("serve_shed_deadline_total") - s0 == 1
    # the expired request never cost device time; the live one answered
    assert fine.reply.status == 200
    coal.close()


def test_bounded_queue_rejects_at_depth():
    cluster = build_cluster()
    session = Session(cluster)
    coal = Coalescer(session, max_batch=4, queue_depth=2)
    coal.hold = threading.Event()  # never released: queue only fills
    coal.start()
    s0 = COUNTERS.get("serve_shed_overload_total")
    p1 = PendingRequest(request=request_of("q1", 1), budget=Budget(None))
    p2 = PendingRequest(request=request_of("q2", 1), budget=Budget(None))
    p3 = PendingRequest(request=request_of("q3", 1), budget=Budget(None))
    assert coal.submit(p1) and coal.submit(p2)
    assert not coal.submit(p3), "queue beyond depth must reject"
    assert COUNTERS.get("serve_shed_overload_total") - s0 == 1
    assert coal.retry_after_s() >= 1
    # cleanup: drain the held queue via the timeout-shed path
    assert coal.drain(timeout=0.05) is False
    assert p1.reply.status == 503 and json.loads(p1.reply.body)["reason"] == "drain"
    coal.hold.set()


# -- HTTP surface ----------------------------------------------------------


@pytest.fixture(scope="module")
def daemon():
    cluster = build_cluster()
    session = Session(cluster)
    d = ServeDaemon(
        session, port=0, max_batch=4, queue_depth=8, drain_timeout_s=10.0
    )
    d.start()
    yield d, cluster
    d.shutdown()


def _post(base, payload: dict, timeout=120):
    req = urllib.request.Request(
        base + "/v1/simulate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def test_http_simulate_conformance_and_metrics(daemon):
    d, cluster = daemon
    base = f"http://{d.host}:{d.port}"
    health = json.load(urllib.request.urlopen(base + "/healthz", timeout=30))
    assert health["ok"] and health["cluster"] == d.session.fingerprint

    # a Deployment JSON doc is valid YAML — one app, 3 replicas
    wire_req = request_of("web", 3)
    resp = _post(
        base,
        {
            "apps": [{"name": "web", "yaml": json.dumps(deployment("web", 3))}],
            "trace": True,
        },
    )
    assert resp.status == 200
    assert resp.headers["X-Simon-Engine"] == "coalesced-scan"
    assert json.loads(resp.headers["X-Simon-Trace"])["batchSize"] >= 1
    assert resp.read() == serial_body(cluster, wire_req)

    metrics = urllib.request.urlopen(base + "/metrics", timeout=30).read().decode()
    for name in (
        "simon_serve_requests_total",
        "simon_serve_shed_total",
        "simon_serve_device_dispatches_total",
        "simon_serve_queue_depth",
        "simon_serve_batch_fill_mean",
        "simon_serve_qps",
        "simon_serve_latency_p50_seconds",
        "simon_serve_latency_p95_seconds",
    ):
        assert f"\n{name} " in "\n" + metrics or metrics.startswith(f"{name} ")


def test_http_concurrent_requests_byte_identical(daemon):
    d, cluster = daemon
    base = f"http://{d.host}:{d.port}"
    reqs = [request_of(f"conc-{i}", 2 + i) for i in range(4)]
    bodies = [None] * len(reqs)
    errors = []

    def worker(i):
        try:
            resp = _post(
                base,
                {
                    "apps": [
                        {
                            "name": f"conc-{i}",
                            "yaml": json.dumps(deployment(f"conc-{i}", 2 + i)),
                        }
                    ]
                },
            )
            bodies[i] = resp.read()
        except Exception as e:  # noqa: BLE001 - collected and asserted below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    for i, req in enumerate(reqs):
        assert bodies[i] == serial_body(cluster, req)


def test_http_bad_request_is_400(daemon):
    d, _ = daemon
    base = f"http://{d.host}:{d.port}"
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(base, {"apps": []})
    assert exc.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(base, {"apps": [{"name": "x", "yaml": ":\nnot yaml: ["}]})
    assert exc.value.code == 400


def test_parse_request_body_raw_yaml():
    req, deadline, trace = parse_request_body(
        json.dumps(deployment("raw", 2)).encode(), "application/yaml"
    )
    assert deadline is None and trace is False
    assert len(req.apps) == 1 and req.apps[0].resource.deployments


def test_parse_request_body_sniffs_json_envelope_without_content_type():
    """A JSON envelope sent without a JSON Content-Type must still be
    treated as the envelope (deadline honored), never YAML-decoded
    into an empty workload answered 200 'success'."""
    body = json.dumps(
        {
            "apps": [{"name": "web", "yaml": json.dumps(deployment("web", 2))}],
            "deadlineSeconds": 5,
        }
    ).encode()
    req, deadline, _ = parse_request_body(body, "")
    assert deadline == 5.0
    assert req.apps[0].resource.deployments


def test_parse_request_body_rejects_empty_decode():
    """YAML that parses but contains no recognized k8s objects is a
    malformed request (400), not an empty simulation (200)."""
    with pytest.raises(ValueError, match="no recognized Kubernetes"):
        parse_request_body(b'{"kind": "NotAThing"}', "application/yaml")


# -- lifecycle -------------------------------------------------------------


def _write_serve_config(tmp_path):
    cluster_dir = tmp_path / "cluster"
    cluster_dir.mkdir()
    (cluster_dir / "nodes.yaml").write_text(
        json.dumps(make_node("solo-node", 8, 32))
    )
    cfg = tmp_path / "serve-config.yaml"
    cfg.write_text(
        "apiVersion: simon/v1alpha1\n"
        "kind: Config\n"
        "metadata: {name: serve-test}\n"
        "spec:\n"
        f"  cluster: {{customConfig: {cluster_dir} }}\n"
    )
    return cfg


def test_sigterm_drains_inflight_and_exits_zero(tmp_path):
    """The daemon process answers an in-flight request after SIGTERM
    (drain, not abort) and exits 0."""
    cfg = _write_serve_config(tmp_path)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu"})
    stderr_path = tmp_path / "serve-stderr.log"
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "open_simulator_tpu.cli",
            "serve",
            "-f",
            str(cfg),
            "--port",
            "0",
            "--no-warm",
            "--drain-timeout",
            "60",
        ],
        stdout=subprocess.PIPE,
        stderr=open(stderr_path, "w"),
        env=env,
        text=True,
    )
    try:
        ready = proc.stdout.readline()
        if "listening on http://" not in ready:
            proc.wait(timeout=30)
            raise AssertionError(
                f"no readiness line: {ready!r} (rc={proc.poll()}, stderr "
                f"tail: {stderr_path.read_text()[-2000:]!r})"
            )
        base = ready.split("listening on ", 1)[1].split()[0].rstrip("/")
        result = {}

        def client():
            resp = _post(
                base,
                {"apps": [{"name": "w", "yaml": json.dumps(deployment("w", 2))}]},
                timeout=180,
            )
            result["status"] = resp.status
            result["body"] = resp.read()

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.2)  # request in flight (likely compiling)
        proc.send_signal(signal.SIGTERM)
        t.join(timeout=180)
        assert result.get("status") == 200, f"in-flight request lost: {result}"
        assert json.loads(result["body"])["success"] is True
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stdout.close()


def test_drain_timeout_exit_code_is_partial():
    """A drain that cannot finish sheds the leftovers and reports exit
    3 (the deadline-partial code) instead of pretending success."""
    cluster = build_cluster()
    session = Session(cluster)
    d = ServeDaemon(
        session, port=0, max_batch=4, queue_depth=8, drain_timeout_s=0.05
    )
    d.coalescer.hold = threading.Event()  # dispatcher never runs
    d.start()
    stuck = PendingRequest(request=request_of("stuck", 1), budget=Budget(None))
    assert d.coalescer.submit(stuck)
    code = d.shutdown()
    assert code == 3
    assert stuck.reply.status == 503
    assert json.loads(stuck.reply.body)["reason"] == "drain"
    d.coalescer.hold.set()


# -- thread-safety satellites ----------------------------------------------


def test_trace_snapshot_is_atomic_under_concurrent_writers():
    """as_dict/as_json take the writer lock: hammering notes and phases
    from threads while serializing must never raise (RuntimeError:
    dict changed size during iteration) and always yields valid JSON."""
    from open_simulator_tpu.utils.trace import Trace

    tr = Trace()
    stop = threading.Event()
    errors = []

    def writer(k):
        i = 0
        while not stop.is_set():
            tr.add(f"phase-{k}-{i % 17}", 0.001)
            tr.append_note(f"note-{k}", f"v{i}")
            i += 1

    def reader():
        while not stop.is_set():
            try:
                json.loads(tr.as_json())
            except Exception as e:  # noqa: BLE001 - the assertion surface
                errors.append(e)
                return

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(3)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors


def test_identity_memo_concurrent_get_and_clear():
    from open_simulator_tpu.utils.memo import IdentityMemo

    memo = IdentityMemo(max_entries=64)
    sources = [({"k": i},) for i in range(256)]
    errors = []
    stop = threading.Event()

    def getter():
        i = 0
        while not stop.is_set():
            s = sources[i % len(sources)]
            try:
                assert memo.get(s, lambda: i) is not None
            except Exception as e:  # noqa: BLE001 - the assertion surface
                errors.append(e)
                return
            i += 1

    def clearer():
        while not stop.is_set():
            memo.clear()

    threads = [threading.Thread(target=getter) for _ in range(3)]
    threads.append(threading.Thread(target=clearer))
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors


def test_name_counter_state_round_trip():
    from open_simulator_tpu.models import workloads as wl

    wl.reset_name_counter()
    a = wl._hash_suffix(8)
    state = wl.name_counter_state()
    b = wl._hash_suffix(8)
    wl.set_name_counter(state)
    assert wl._hash_suffix(8) == b
    wl.reset_name_counter()
    assert wl._hash_suffix(8) == a
