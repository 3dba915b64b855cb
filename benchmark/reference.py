"""The plain reference scheduler: one kube-scheduler v1.20 cycle per pod,
as the simulator documents it, in straightforward jax.numpy.

It imports nothing of the program. Its inputs are the tables that
``scenario.py`` builds from the configuration and traffic files. One
``lax.scan`` step places one pod: filter every node, score the
feasible ones, take the first maximum in node order (the simulator's
deterministic selectHost), commit.

Filters: NodeResourcesFit (pods, cpu, memory), TaintToleration
(NoSchedule), PodTopologySpread (DoNotSchedule over a zone key),
InterPodAffinity (required anti-affinity on the hostname, both ways).

Scores that differ between nodes for these pods:
NodeResourcesBalancedAllocation (float64 fractions, truncated),
NodeResourcesLeastAllocated (integer), and the Simon and
Open-Gpu-Share plugins (the same min-max normalized share, weight 1
each). Every other plugin of the profile gives the same score to every
node for these pods (no images, no preferred terms, no
PreferNoSchedule taints, no soft spread constraints, no local
volumes), so it cannot change the choice and is left out.

Precision: quantities are int64 (millicores, bytes) and fractions are
float64, as the configuration files state, computed on the host's CPU
so that no chip's emulation of float64 enters the reference. ``precision="low"`` runs
the same code in int32 and float32: the control that must fail.
``precision="f32"`` keeps int64 and takes the fractions in float32,
a reading kept beside it (PERF.md).
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from .scenario import HOSTNAME_KEY, PodClass, Tables

MAX_SCORE = 100


def simon_raw(tables: Tables, classes: Sequence[PodClass], np_float) -> np.ndarray:
    """[C, N]: int(100 * max over the node's allocatable resources of
    request / (allocatable - request)), cpu in cores, memory in bytes,
    pods counted 0 (a pod requests no `pods` resource)."""
    cores = tables.alloc_cpu.astype(np_float) / np_float(1000)
    mem = tables.alloc_mem.astype(np_float)
    out = np.zeros((len(classes), len(tables.names)), np.int64)
    for c, pc in enumerate(classes):
        rc = np_float(pc.cpu_m) / np_float(1000)
        rm = np_float(pc.mem)
        share = np.maximum(rc / (cores - rc), rm / (mem - rm))
        out[c] = (np_float(MAX_SCORE) * share).astype(np.int64)
    return out


def _groups(classes, key):
    """Each class that carries a constraint owns one group; returns
    (group of class or 0, has, group list)."""
    owners = [c for c, pc in enumerate(classes) if getattr(pc, key)]
    grp = np.zeros(len(classes), np.int64)
    for g, c in enumerate(owners):
        grp[c] = g
    has = np.array([bool(getattr(pc, key)) for pc in classes])
    return grp, has, owners or [None]


def _matches(selector: dict, labels: dict) -> bool:
    return all(labels.get(k) == v for k, v in selector.items())


def build_inputs(tables: Tables, classes: List[PodClass], bound, taint_keys,
                 zone_key, precision: str = "high"):
    """Static arrays and the initial state (running pods committed)."""
    np_int = np.int32 if precision == "low" else np.int64
    np_float = np.float64 if precision == "high" else np.float32
    n = len(tables.names)
    c_n = len(classes)
    for pc in classes:
        if pc.spread and pc.spread["topologyKey"] != zone_key:
            raise ValueError("the reference spreads over the zone key only")
        if pc.anti and pc.anti["topologyKey"] != HOSTNAME_KEY:
            raise ValueError("the reference's anti-affinity is per hostname only")
    # taints: a node with taint key k admits class c only if c tolerates k
    static_ok = np.ones((c_n, n), bool)
    for c, pc in enumerate(classes):
        for k, key in enumerate(taint_keys):
            if key not in pc.tolerates:
                static_ok[c] &= tables.taint != k
    n_zones = int(tables.zone.max()) + 1 if (tables.zone >= 0).any() else 1
    zone_present = np.zeros(n_zones, bool)
    zone_present[tables.zone[tables.zone >= 0]] = True

    sgrp, has_spread, s_owners = _groups(classes, "spread")
    agrp, has_anti, a_owners = _groups(classes, "anti")
    match_s = np.zeros((len(s_owners), c_n), np.int64)
    for g, o in enumerate(s_owners):
        if o is None:
            continue
        sel = classes[o].spread["selector"]
        for c, pc in enumerate(classes):
            # countPodsMatchSelector: same namespace, labels match
            match_s[g, c] = int(pc.namespace == classes[o].namespace and _matches(sel, pc.labels))
    match_a = np.zeros((len(a_owners), c_n), np.int64)
    for a, o in enumerate(a_owners):
        if o is None:
            continue
        term = classes[o].anti
        for c, pc in enumerate(classes):
            match_a[a, c] = int(pc.namespace in term["namespaces"] and _matches(term["selector"], pc.labels))
    skew = np.array([int(pc.spread["maxSkew"]) if pc.spread else 0 for pc in classes])

    used_cpu = np.zeros(n, np.int64)
    used_mem = np.zeros(n, np.int64)
    used_pods = np.zeros(n, np.int64)
    zone_cnt = np.zeros((len(s_owners), n_zones), np.int64)
    anti_cnt = np.zeros((len(a_owners), n), np.int64)
    term_cnt = np.zeros((len(a_owners), n), np.int64)
    if len(bound):
        b = np.asarray(bound, np.int64).reshape(-1, 2)
        cls, node = b[:, 0], b[:, 1]
        cpu = np.array([pc.cpu_m for pc in classes], np.int64)
        mem = np.array([pc.mem for pc in classes], np.int64)
        np.add.at(used_cpu, node, cpu[cls])
        np.add.at(used_mem, node, mem[cls])
        np.add.at(used_pods, node, 1)
        z = tables.zone[node]
        for g in range(len(s_owners)):
            np.add.at(zone_cnt[g], z[z >= 0], match_s[g, cls[z >= 0]])
        for a in range(len(a_owners)):
            np.add.at(anti_cnt[a], node, match_a[a, cls])
            own = has_anti[cls] & (agrp[cls] == a)
            np.add.at(term_cnt[a], node[own], 1)

    cast = lambda a: a.astype(np_int)  # noqa: E731 - int32 wraps, as the control should
    static = dict(
        alloc_cpu=cast(tables.alloc_cpu), alloc_mem=cast(tables.alloc_mem),
        alloc_pods=cast(tables.alloc_pods), zone=tables.zone.astype(np.int32),
        static_ok=static_ok, zone_present=zone_present,
        cpu=cast(np.array([pc.cpu_m for pc in classes], np.int64)),
        mem=cast(np.array([pc.mem for pc in classes], np.int64)),
        simon=simon_raw(tables, classes, np_float).astype(np_int),
        has_spread=has_spread, sgrp=sgrp.astype(np.int32), skew=cast(skew),
        match_s=cast(match_s), has_anti=has_anti, agrp=agrp.astype(np.int32),
        match_a=cast(match_a),
    )
    state = (cast(used_cpu), cast(used_mem), cast(used_pods), cast(zone_cnt),
             cast(anti_cnt), cast(term_cnt))
    return static, state, np_float


def _run(s, init, seq, jnp_float):
    """lax.scan of one pod step per element of `seq`. The tables are
    arguments, not constants, so one compiled program serves every
    seed of a cell (the persistent cache then hits)."""
    import jax
    import jax.numpy as jnp

    idt = s["alloc_cpu"].dtype
    big = jnp.asarray(np.iinfo(np.int32).max, idt)

    def step(state, c):
        used_cpu, used_mem, used_pods, zone_cnt, anti_cnt, term_cnt = state
        rc, rm = s["cpu"][c], s["mem"][c]
        req_cpu = used_cpu + rc
        req_mem = used_mem + rm
        feas = (
            (used_pods + 1 <= s["alloc_pods"])
            & (req_cpu <= s["alloc_cpu"])
            & (req_mem <= s["alloc_mem"])
            & s["static_ok"][c]
        )
        # PodTopologySpread filter: skew of the node's zone against the
        # emptiest zone among nodes that carry the key
        g = s["sgrp"][c]
        cnt = zone_cnt[g]
        min_cnt = jnp.min(jnp.where(s["zone_present"], cnt, big))
        zone = s["zone"]
        node_cnt = cnt[jnp.maximum(zone, 0)]
        spread_ok = (zone >= 0) & (node_cnt + s["match_s"][g, c] - min_cnt <= s["skew"][c])
        feas &= jnp.where(s["has_spread"][c], spread_ok, True)
        # InterPodAffinity: the pod's own term, then existing pods' terms
        feas &= jnp.where(s["has_anti"][c], anti_cnt[s["agrp"][c]] == 0, True)
        feas &= ~jnp.any((term_cnt > 0) & (s["match_a"][:, c][:, None] > 0), axis=0)

        acpu, amem = s["alloc_cpu"], s["alloc_mem"]
        least = (
            jnp.where(req_cpu > acpu, 0, (acpu - req_cpu) * MAX_SCORE // acpu)
            + jnp.where(req_mem > amem, 0, (amem - req_mem) * MAX_SCORE // amem)
        ) // 2
        cf = req_cpu.astype(jnp_float) / acpu.astype(jnp_float)
        mf = req_mem.astype(jnp_float) / amem.astype(jnp_float)
        bal = jnp.where(
            (cf >= 1) | (mf >= 1),
            0,
            jnp.floor((1 - jnp.abs(cf - mf)) * MAX_SCORE).astype(idt),
        )
        raw = s["simon"][c]
        hi = jnp.max(jnp.where(feas, raw, -big))
        lo = jnp.min(jnp.where(feas, raw, big))
        rng = jnp.maximum(hi - lo, 1)
        simon = jnp.where(hi > lo, (raw - lo) * MAX_SCORE // rng, 0)
        score = jnp.where(feas, least + bal + 2 * simon, -1)
        idx = jnp.argmax(score)
        placed = feas[idx]
        hit = (jnp.arange(score.shape[0]) == idx) & placed
        one = hit.astype(idt)
        used_cpu = used_cpu + one * rc
        used_mem = used_mem + one * rm
        used_pods = used_pods + one
        zid = zone[idx]
        zhit = (jnp.arange(zone_cnt.shape[1]) == zid) & placed & (zid >= 0)
        zone_cnt = zone_cnt + s["match_s"][:, c][:, None] * zhit.astype(idt)[None, :]
        anti_cnt = anti_cnt + s["match_a"][:, c][:, None] * one[None, :]
        own = (jnp.arange(term_cnt.shape[0]) == s["agrp"][c]) & s["has_anti"][c]
        term_cnt = term_cnt + own.astype(idt)[:, None] * one[None, :]
        out = jnp.where(placed, idx, -1).astype(jnp.int32)
        return (used_cpu, used_mem, used_pods, zone_cnt, anti_cnt, term_cnt), out

    return jax.lax.scan(step, init, seq)[1]


_RUN = None


def _run_jit():
    """The jitted scan, built on first use: importing this module starts
    no JAX work."""
    global _RUN
    if _RUN is None:
        import jax

        _RUN = jax.jit(_run, static_argnums=3)
    return _RUN


def cpu_device():
    """The reference runs on the host's CPU in IEEE float64, whatever
    the chip under test emulates."""
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise SystemExit(f"benchmark: the reference needs JAX's CPU backend ({e})")


class Reference:
    """The reference over one cluster and its running pods; `schedule`
    places one app's pods (class ids in scheduling order) from that
    state. Runs on the CPU (`cpu_device`)."""

    def __init__(self, tables: Tables, classes: List[PodClass], bound, taint_keys,
                 zone_key, precision: str = "high"):
        import jax
        import jax.numpy as jnp

        self.x64 = precision != "low"
        self.cpu = cpu_device()
        static, state, _ = build_inputs(tables, classes, bound, taint_keys,
                                        zone_key, precision)
        with jax.enable_x64(self.x64):
            self.static = {k: jax.device_put(v, self.cpu) for k, v in static.items()}
            self.init = tuple(jax.device_put(v, self.cpu) for v in state)
        self.float = jnp.float64 if precision == "high" else jnp.float32

    def schedule(self, seq: Sequence[int]) -> np.ndarray:
        import jax

        with jax.enable_x64(self.x64), jax.default_device(self.cpu):
            seq = jax.device_put(np.asarray(seq, np.int32), self.cpu)
            out = _run_jit()(self.static, self.init, seq, self.float)
            return np.asarray(out).astype(np.int64)


def schedule(tables: Tables, classes: List[PodClass], bound, seq: Sequence[int],
             taint_keys, zone_key, precision: str = "high") -> np.ndarray:
    """Placement (node index or -1) of every pod of `seq` (class ids, in
    scheduling order), after the `bound` (class, node) pods are
    committed."""
    return Reference(tables, classes, bound, taint_keys, zone_key, precision).schedule(seq)


def exceeds_capacity(tables: Tables, classes: List[PodClass], seq: Sequence[int]) -> bool:
    """True where the pods of `seq` ask more cpu, memory or pod slots
    than all the nodes hold together: then no scheduler places them
    all, and the reference need not run to show it."""
    seq = np.asarray(seq, np.int64)
    cpu = np.array([pc.cpu_m for pc in classes], np.int64)[seq].sum()
    mem = np.array([pc.mem for pc in classes], np.int64)[seq].sum()
    return bool(cpu > tables.alloc_cpu.sum() or mem > tables.alloc_mem.sum()
                or len(seq) > tables.alloc_pods.sum())


def counts(n_nodes: int, n_classes: int, bound, seq, placements) -> np.ndarray:
    """[N, C] pods of each class on each node, running pods included."""
    out = np.zeros((n_nodes, n_classes), np.int64)
    if len(bound):
        b = np.asarray(bound, np.int64).reshape(-1, 2)
        np.add.at(out, (b[:, 1], b[:, 0]), 1)
    seq = np.asarray(seq, np.int64)
    placements = np.asarray(placements, np.int64)
    ok = placements >= 0
    np.add.at(out, (placements[ok], seq[ok]), 1)
    return out
