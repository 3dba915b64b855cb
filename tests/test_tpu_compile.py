"""The device programs compile for a described TPU v5e at real widths.

No chip is needed: libtpu's compiler compiles for a chip that is
described and not attached (jax.experimental.topologies). This is what
interpret mode cannot show — Mosaic refusing a tiling or a VMEM budget
— checked for every kernel layout the capacity plan and the bench use,
and for the node-sharded scan on a 2x2 mesh.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load libtpu, and every xdist
worker imports this file.
"""

import numpy as np
import pytest

import bench


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe the chip is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _plan(nodes, pods):
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.ops.encode import (
        encode_batch,
        encode_cluster,
        encode_dynamic,
        features_of_batch,
    )
    from open_simulator_tpu.scheduler.oracle import Oracle

    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    plan = pallas_scan.build_plan(
        cluster, batch, dyn, features_of_batch(cluster, batch)
    )
    assert plan is not None, pallas_scan.last_reject()
    return plan, len(pods)


def _capacity_plan():
    from open_simulator_tpu.apply.applier import MAX_NUM_NEW_NODE
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.parallel.sweep import CapacitySweep

    cluster, apps, new_node = bench.build_capacity_scenario()
    prev = pallas_scan.FORCE_ENABLE
    pallas_scan.FORCE_ENABLE = True
    try:
        sweep = CapacitySweep(cluster, apps, new_node, MAX_NUM_NEW_NODE)
    finally:
        pallas_scan.FORCE_ENABLE = prev
    assert sweep._pallas_plan is not None, pallas_scan.last_reject()
    return sweep._pallas_plan, len(sweep.pods)


def _preempt_plan():
    """PreemptionBasic 5000Nodes' app batch: 5,000 priority-10 pods of
    3000m over 5,000 nodes, the dry run's slots at their real width
    (ops/preempt.table_slots gives 8 there; the content does not change
    the program)."""
    from open_simulator_tpu.ops import pallas_scan
    from open_simulator_tpu.ops.encode import (
        encode_batch,
        encode_cluster,
        encode_dynamic,
        features_of_batch,
    )
    from open_simulator_tpu.ops.preempt import _FIELDS
    from open_simulator_tpu.scheduler.oracle import Oracle
    from open_simulator_tpu.testing import make_fake_node, make_fake_pod, with_priority

    nodes = [make_fake_node(f"node-{i}", "4", "32Gi") for i in range(5000)]
    pods = [make_fake_pod(f"high-{i}", "default", "3000m", "500Mi", with_priority(10))
            for i in range(5000)]
    oracle = Oracle(nodes)
    cluster = encode_cluster(oracle)
    batch = encode_batch(oracle, cluster, pods)
    dyn = encode_dynamic(oracle, cluster)
    k, n = 8, len(nodes)
    table = {f: np.zeros((k, n), np.int64) for f in _FIELDS}
    table.update(valid=np.zeros((k, n), bool), hard=np.zeros((k, n), bool))
    plan = pallas_scan.build_plan(
        cluster, batch, dyn, features_of_batch(cluster, batch)._replace(preempt=True),
        preempt=(table, 1, np.full(len(pods), 10), np.ones(len(pods), bool),
                 np.zeros(len(pods), bool)),
    )
    assert plan is not None and plan.pre.k == k, pallas_scan.last_reject()
    return plan, len(pods)


LAYOUTS = {
    # the flagship: 100k pods over 10k nodes + the new-node padding
    "capacity-100k": (_capacity_plan, "pallas"),
    "resident-base-10k": (lambda: _plan(*bench.build_scenario()), "pallas"),
    "resident-terms-2k": (
        lambda: _plan(*bench.build_affinity_scenario()), "pallas"
    ),
    "streamed-terms-25k": (
        lambda: _plan(
            *bench.build_affinity_scenario(n_nodes=25_000, replicas=100)
        ),
        "pallas-stream",
    ),
    "open-local-10k": (
        lambda: _plan(*bench.build_storage_scenario()), "pallas"
    ),
    "gpushare-1k": (
        lambda: _plan(*bench.build_gpushare_scenario()), "pallas"
    ),
    "preempt-5k": (_preempt_plan, "pallas"),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_compiles_for_v5e(one_chip, layout):
    import jax
    import jax.numpy as jnp

    from open_simulator_tpu.ops import pallas_scan

    build, label = LAYOUTS[layout]
    plan, p_total = build()
    assert pallas_scan.kernel_label(plan) == label
    metas = pallas_scan._plan_metas(pallas_scan._plan_args_np(plan))
    call = pallas_scan.kernel_call(plan, p_total, metas, interpret=False)
    pr_rows = pallas_scan._pr_rows(p_total)
    n_percall = (9 * pr_rows + plan.r) * pallas_scan.LANES
    n_flat = sum(int(np.prod(shape)) for shape, _ in metas)
    with jax.enable_x64(False):
        lowered = call.lower(
            jax.ShapeDtypeStruct((n_percall,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((n_flat,), jnp.int32, sharding=one_chip),
        )
        assert "tpu_custom_call" in lowered.as_text()
        # raises what the chip's compiler would raise (tiling, VMEM)
        lowered.compile()


def test_node_sharded_scan_compiles_for_four_v5e_chips(topo):
    """The node-axis shard_map scan on a 2x2 v5e mesh. The chip lowers
    only SUM all-reduces of s64, so an int64 pmax/pmin in the shard
    context is refused here (the CPU mesh accepts it)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from open_simulator_tpu.parallel import mesh as mesh_mod
    from open_simulator_tpu.parallel.sweep import CapacitySweep

    cluster, apps, _ = bench.build_capacity_scenario()
    cluster.nodes = cluster.nodes[:1000]
    sweep = CapacitySweep(cluster, apps, None, 0)
    mesh = Mesh(np.array(topo.devices), (mesh_mod.MESH_AXIS,))
    replicated = NamedSharding(mesh, PartitionSpec())

    def shape(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated)

    shards = len(topo.devices)
    args = (
        jax.tree_util.tree_map(shape, mesh_mod.pad_static(sweep.static, shards)),
        jax.tree_util.tree_map(shape, mesh_mod.pad_state(sweep.init, shards)),
        shape(sweep.batch.class_of_pod),
        shape(sweep.batch.pinned_node),
        shape(mesh_mod.pad_valid(sweep.node_valid(0), shards)),
        shape(np.ones(len(sweep.pods), bool)),
    )
    jitted = mesh_mod._mesh_scan_jit(mesh)._fn
    jitted.lower(sweep.features, *args).compile()
