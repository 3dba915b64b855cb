"""Workload -> pod expansion invariants, mirroring the replica-count
checks of the reference unit test (pkg/simulator/core_test.go:364-591
checkResult)."""

import os

from open_simulator_tpu.models.decode import load_directory
from open_simulator_tpu.models import workloads as wl

SIMPLE = os.path.join(os.path.dirname(__file__), "data", "reference", "application", "simple")


def _simple():
    return load_directory(SIMPLE)


def test_deployment_expansion_count_and_metadata():
    res = _simple()
    deploy = next(d for d in res.deployments if d["metadata"]["name"] == "busybox-deploy")
    pods = wl.pods_from_deployment(deploy)
    assert len(pods) == deploy["spec"]["replicas"]
    for p in pods:
        assert p["metadata"]["namespace"] == "simple"
        # labels come from the OWNER object, not the template
        assert p["metadata"]["labels"]["app"] == "busybox-deploy"
        assert p["metadata"]["annotations"][wl.ANNO_WORKLOAD_KIND] == "ReplicaSet"
        assert p["spec"]["schedulerName"] == "default-scheduler"
        assert p["spec"]["dnsPolicy"] == "ClusterFirst"
        # tolerations preserved from the template spec
        assert p["spec"]["tolerations"][0]["key"] == "node-role.kubernetes.io/master"


def test_statefulset_ordinal_names_and_storage_annotation():
    sts = {
        "kind": "StatefulSet",
        "metadata": {"name": "db", "namespace": "x", "labels": {"app": "db"}},
        "spec": {
            "replicas": 3,
            "template": {"spec": {"containers": [{"name": "c", "image": "img"}]}},
            "volumeClaimTemplates": [
                {
                    "spec": {
                        "storageClassName": "open-local-lvm",
                        "resources": {"requests": {"storage": "10Gi"}},
                    }
                }
            ],
        },
    }
    pods = wl.pods_from_stateful_set(sts)
    assert [p["metadata"]["name"] for p in pods] == ["db-0", "db-1", "db-2"]
    import json

    vols = json.loads(pods[0]["metadata"]["annotations"][wl.ANNO_POD_LOCAL_STORAGE])
    assert vols["volumes"] == [
        {"size": str(10 * 1024**3), "kind": "LVM", "scName": "open-local-lvm"}
    ]


def test_job_completions():
    job = {
        "kind": "Job",
        "metadata": {"name": "j"},
        "spec": {
            "completions": 5,
            "template": {"spec": {"containers": [{"name": "c", "image": "i"}]}},
        },
    }
    assert len(wl.pods_from_job(job)) == 5


def test_pvc_volume_rewritten_to_hostpath():
    pod = {
        "metadata": {"name": "p"},
        "spec": {
            "containers": [{"name": "c", "image": "i"}],
            "volumes": [{"name": "v", "persistentVolumeClaim": {"claimName": "x"}}],
        },
    }
    out = wl.make_valid_pod(pod)
    assert out["spec"]["volumes"][0]["hostPath"] == {"path": "/tmp"}
    assert "persistentVolumeClaim" not in out["spec"]["volumes"][0]


def test_daemonset_pins_and_skips_ineligible_nodes():
    res = _simple()
    ds = next(d for d in res.daemon_sets if d["metadata"]["name"] == "busybox-ds")
    master = {
        "metadata": {
            "name": "m1",
            "labels": {"node-role.kubernetes.io/master": "", "beta.kubernetes.io/os": "linux"},
        }
    }
    worker = {
        "metadata": {"name": "w1", "labels": {"beta.kubernetes.io/os": "linux"}}
    }
    tainted = {
        "metadata": {"name": "w2", "labels": {"beta.kubernetes.io/os": "linux"}},
        "spec": {"taints": [{"key": "dedicated", "effect": "NoSchedule"}]},
    }
    # the ds requires node-role.kubernetes.io/master DoesNotExist
    pods = wl.pods_from_daemon_set(ds, [master, worker, tainted])
    assert len(pods) == 1
    terms = pods[0]["spec"]["affinity"]["nodeAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"
    ]["nodeSelectorTerms"]
    assert any(
        t.get("matchFields") == [{"key": "metadata.name", "operator": "In", "values": ["w1"]}]
        for t in terms
    )


def test_daemonset_tolerations_allow_tainted_node():
    ds = {
        "kind": "DaemonSet",
        "metadata": {"name": "d", "namespace": "kube-system"},
        "spec": {
            "template": {
                "spec": {
                    "containers": [{"name": "c", "image": "i"}],
                    "tolerations": [{"operator": "Exists"}],
                }
            }
        },
    }
    tainted = {
        "metadata": {"name": "w2", "labels": {}},
        "spec": {"taints": [{"key": "dedicated", "effect": "NoSchedule"}]},
    }
    assert len(wl.pods_from_daemon_set(ds, [tainted])) == 1


# ------------------------------------------------- raw-pod content interning


def _raw_pod(name=None, generate_name=None, cpu="250m", extra=None):
    p = {
        "metadata": {"namespace": "default"},
        "spec": {
            "containers": [
                {"name": "c", "image": "i", "resources": {"requests": {"cpu": cpu}}}
            ]
        },
    }
    if name:
        p["metadata"]["name"] = name
    if generate_name:
        p["metadata"]["generateName"] = generate_name
    if extra:
        p.update(extra)
    return p


def test_raw_pod_interning_shares_spec_but_not_annotations():
    from open_simulator_tpu.models.decode import ResourceTypes

    res = ResourceTypes(pods=[_raw_pod(f"p-{i}") for i in range(4)])
    pods = wl.pods_excluding_daemon_sets(res)
    assert [p["metadata"]["name"] for p in pods] == [f"p-{i}" for i in range(4)]
    # spec content shared by identity (the encode class-key memo relies
    # on it), annotations per-pod (the GPU binder mutates them)
    assert pods[1]["spec"]["containers"] is pods[0]["spec"]["containers"]
    assert pods[1]["metadata"]["annotations"] is not pods[0]["metadata"]["annotations"]
    # top-level spec dict is per-pod: a bind's nodeName write must not leak
    pods[1]["spec"]["nodeName"] = "n1"
    assert "nodeName" not in pods[0]["spec"]
    assert "nodeName" not in pods[2]["spec"]


def test_template_replicas_share_one_labels_dict():
    """Template replicas deliberately share ONE labels dict (and one
    ownerReferences list): correctness rests on the invariant that the
    only post-expansion label write is the uniform app-name stamp
    (generate_valid_pods_from_app). This test pins the shared identity
    so a future per-pod label writer fails here loudly instead of
    silently aliasing across 100k pods (workloads._expand_template)."""
    from open_simulator_tpu.models.decode import ResourceTypes

    res = ResourceTypes()
    res.deployments = [
        {
            "kind": "Deployment",
            "metadata": {"name": "web", "namespace": "d"},
            "spec": {
                "replicas": 4,
                "template": {
                    "metadata": {"labels": {"app": "web"}},
                    "spec": {"containers": [{"name": "c", "image": "i"}]},
                },
            },
        }
    ]
    nodes = [
        {
            "kind": "Node",
            "metadata": {"name": "n0", "labels": {}},
            "status": {
                "allocatable": {"cpu": "8", "memory": "16Gi", "pods": "110"}
            },
        }
    ]
    pods = wl.generate_valid_pods_from_app("demo", res, nodes)
    assert len(pods) == 4
    first = pods[0]["metadata"]
    for p in pods[1:]:
        meta = p["metadata"]
        assert meta["labels"] is first["labels"]
        assert meta["ownerReferences"] is first["ownerReferences"]
    # the one sanctioned post-expansion write landed uniformly
    assert first["labels"][wl.LABEL_APP_NAME] == "demo"


def test_raw_pod_interning_generate_name_only():
    from open_simulator_tpu.models.decode import ResourceTypes

    res = ResourceTypes(
        pods=[_raw_pod(generate_name="web-"), _raw_pod(generate_name="web-")]
    )
    pods = wl.pods_excluding_daemon_sets(res)
    assert len(pods) == 2
    for p in pods:
        assert p["metadata"]["generateName"] == "web-"


def test_raw_pod_interning_keys_on_all_top_level_fields():
    from open_simulator_tpu.models.decode import ResourceTypes

    a = _raw_pod("a")
    b = _raw_pod("b", extra={"apiVersion": "v1", "kind": "Pod"})
    res = ResourceTypes(pods=[a, b])
    pods = wl.pods_excluding_daemon_sets(res)
    by_name = {p["metadata"]["name"]: p for p in pods}
    # differing top-level fields -> different intern groups; b keeps its own
    assert by_name["b"].get("kind") == "Pod"
    assert "kind" not in by_name["a"]


def test_raw_pod_interning_rejects_nameless_duplicates():
    import pytest as _pytest

    from open_simulator_tpu.models.decode import ResourceTypes
    from open_simulator_tpu.models.validation import InputError

    named = _raw_pod("ok")
    nameless = _raw_pod()  # no name, no generateName
    res = ResourceTypes(pods=[named, nameless])
    with _pytest.raises(InputError):
        wl.pods_excluding_daemon_sets(res)
