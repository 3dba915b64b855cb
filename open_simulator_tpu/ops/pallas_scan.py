"""Fused Pallas TPU kernel for the sequential-commit scheduling scan.

The XLA `lax.scan` step (ops/scan.py) lowers to ~15-20 small kernels
per pod; at N=10k nodes each is latency-bound (~2-3us), so a 100k-pod
capacity probe costs ~3-4 s on a v5e chip. This module runs the ENTIRE
scan inside ONE `pl.pallas_call`: a `fori_loop` over pods with all
cluster state resident in VMEM as (R, 128) int32 tiles — per-step cost
collapses to pure VPU arithmetic with zero kernel-launch overhead.

Scope (automatic fallback to the XLA scan otherwise):
- no custom-plugin machinery (features gates, same contract as
  ScanFeatures). nodeName pins (`run_scan_pallas(pinned=...)`),
  hostPorts (per-(ip,proto,port) vocab bitmask tiles), extended
  scalar resources, and open-gpu-share device packing (per-device
  (G, R, 128) memory tiles, tightest-fit / two-pointer allocation
  mirroring scan.py _gpu_allocate; gpu+pins falls back) ARE in scope,
- open-local storage IS in scope (r5): the VG Binpack and device
  first-fit run in GCD-scaled int32, and the f64 ScoreLVM/ScoreDevice
  truncations — r4's measured reason for staying off the kernel —
  ride as host-precomputed SMEM tables indexed by the in-kernel
  assignment pattern (StorePlan docstring),
- inter-pod affinity + hard/soft topology spread ARE in scope: term
  count state rides in VMEM scratch as node-space (T, R, 128) i32
  tiles (ops/scan.py ScanState docstring), per-(class, slot) eval
  scalars are prefolded host-side into SMEM tables, init states stream
  in from ANY/HBM by DMA, and commits are masked broadcasts over
  (topo_val == placed value). Past the VMEM budget the plan
  auto-rewrites to the STREAMED layout (r5): term state lives in one
  HBM buffer and each pod step DMA-gathers only its class's rows
  (StreamTermsPlan docstring) — the ~12.3k-node cliff becomes a
  bandwidth slope (50k nodes measured),
- DefaultPreemption's dry run IS in scope (ops/preempt.py): the
  committed pods ride as (F*K, R, C) slot tiles in VMEM scratch, and a
  pod that fails every node runs the dry run, pick and eviction in the
  same step (dry_run below), up to _PRE_MAX_K slots a node,
- all quantities must fit exactness-preserving int32 encodings:
  memory/ephemeral values are divided by their collective GCD
  (floor-division identities keep every score and fit comparison
  bit-identical to the int64 XLA path), with magnitude guards
  (_build_terms bounds for counts/weights/raw scores).

Semantics replicated from ops/scan.py (which is conformance-tested
against the serial oracle):
- NodeResourcesFit (noderesources/fit.go:230-303) incl. the
  zero-request pod-count-only fast path,
- LeastAllocated / BalancedAllocation / NodeAffinity / TaintToleration
  / Simon / ImageLocality / NodePreferAvoidPods scores with their
  normalizes (normalize_score.go:26-53, simon.go:75-100),
- InterPodAffinity filter/score (filtering.go:241-430, scoring.go) and
  PodTopologySpread hard filter + soft score (podtopologyspread/),
- first-max tie rule over feasible nodes (documented deviation shared
  with the XLA engine, scan.py:19-21),
- capacity-sweep masking: node_valid gates candidates, inactive pods
  commit nothing and report INACTIVE.

Float care: BalancedAllocation runs in f32 (inputs are <=24-bit scaled
integers, fractions exact, only the final truncation is float). The
soft-spread score needs f64 (cnt * log(sz+2)); TPU Pallas has no f64,
so it runs in double-single f32: log tables are precomputed in f64 on
the host and split into (hi, lo) f32 pairs with hi further Veltkamp-
split into 12-bit halves, partial products of the 8/9-bit-split count
are exact in f32, and 2Sum chains carry the compensation — ~2^-45
relative error against the XLA path's f64, far below the integer
truncation granularity. Conformance tests (tests/test_pallas_scan.py,
tests/test_pallas_terms.py) pin agreement with the XLA path.

Every host<->device transfer and blocking fetch has a fixed latency
(its size on today's chip is not measured yet): plan arrays are
device-cached per plan (_device_args), inputs ship as one batched
device_put, and the six state outputs return stacked as a single
fetch.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import NamedTuple, Optional

import numpy as np

LANES = 128
SUBLANES = 8
NEG = -(2**31) + 1
BIG = 2**31 - 1
MAX_SCORE = 100
INACTIVE = -2

# magnitude guards: every intermediate must stay inside int32
_MAX_SCALED = (2**31 - 1) // (MAX_SCORE + 1)


class TermsCfg(NamedTuple):
    """Static shape/slot configuration of the term machinery (part of
    the compiled-kernel cache key)."""

    t: int  # logical term rows (bit positions)
    td: int  # distinct topology tiles
    tc: int  # count-state rows (rows some consumer reads as counts)
    tp: int  # pref-state rows (rows with preferred weights)
    bp: int  # bitplane count = ceil(t / 32)
    a: int  # required-affinity group rows
    gn: int  # group count
    csn: int  # non-hostname soft instances (with dedicated count state)
    cd: int  # distinct hard-spread candidate tiles
    sqd: int  # distinct soft qualifying-node tiles
    hkd: int  # distinct has-all-soft-keys tiles
    rmax: int  # per-class relevant-row slots
    gmax: int  # per-class group-row slots
    hmax: int  # per-class hard slots
    smax: int  # per-class soft slots
    cmax: int  # per-class commit slots
    scmax: int  # per-class non-host soft commit slots
    vs: int  # non-hostname soft vocab size
    has_ipa: bool
    has_hard: bool
    has_soft: bool
    # node-axis streaming (term state in HBM, per-pod row gather):
    # the three fields below are 0/False on resident plans
    stream: bool = False
    kmax: int = 0  # per-class gather slots (max distinct rows fetched)
    wmax: int = 0  # per-class write-back slots (max dirty rows)
    srows: int = 0  # rows of the unified HBM state buffer


class TermsPlan(NamedTuple):
    """Term-machinery arrays for the fused kernel.

    Memory design (v3): count state is kept ONLY for rows some consumer
    reads as counts (score carries, hard/soft spread); rows tested only
    as `> 0` (required anti-affinity existence, own-anti targets) live
    in int32 BITPLANES — exact, because those states are monotone under
    the scan's commit-only updates. Static (R, C) tiles (topology
    values, spread candidates, qualifying nodes, has-keys masks, class
    tables) are deduplicated to their distinct rows with host-resolved
    SMEM indices. Commits are SPARSE: each class carries at most cmax
    (row, update) slots instead of a dense (T, R, C) broadcast. This
    removes the T-proportional VMEM and per-step commit cost that
    barred term-heavy batches at 10k nodes from the fused kernel."""

    cfg: TermsCfg
    # --- VMEM tiles -------------------------------------------------
    topo_dist: np.ndarray  # (Td, R, C) i32 distinct topo values, -1 = missing
    g_topo3: np.ndarray  # (A, R, C) group-row topo values (dense, A small)
    cand_dist: np.ndarray  # (Cd, R, C) distinct hard candidate masks
    sq_dist: np.ndarray  # (Sqd, R, C) distinct soft qualifying masks
    hk_dist: np.ndarray  # (Hkd, R, C) distinct has-all-soft-keys masks
    g_match_au: np.ndarray  # (A, Ur_p, 128) match_all[group_of_row] (commit)
    # --- state inits (ANY memory; DMAed into scratch) ----------------
    tgt0_c: np.ndarray  # (Tc, R, C) init counts for count rows
    pref0_p: np.ndarray  # (Tp, R, C) combined preferred init
    panti0_p: np.ndarray  # (Tp, R, C)
    antib0: np.ndarray  # (Bp, R, C) init anti>0 bitplanes
    tposb0: np.ndarray  # (Bp, R, C) init tgt>0 bitplanes
    group0: np.ndarray  # (A, R, C)
    gtot0: np.ndarray  # (A, 8, 128) per-group-row totals, replicated
    soft0_nh: np.ndarray  # (Csn, R, C) init counts, non-host soft instances
    # --- SMEM eval slot tables (U, Rmax/Gmax/Hmax/Smax) --------------
    e_cnt: np.ndarray  # (U, Rmax) tgt_cnt idx (-1 = no count read)
    e_pref: np.ndarray  # (U, Rmax) pref idx (-1 = no pref read; folds match)
    e_cpd: np.ndarray  # (U, Rmax) carry_aff_pref_w - carry_anti_pref_w
    e_antip: np.ndarray  # (U, Rmax) anti bitplane idx
    e_antib: np.ndarray  # (U, Rmax) anti bitmask (0 = no test; folds m)
    e_tposp: np.ndarray  # (U, Rmax) tgt>0 plane idx
    e_tposb: np.ndarray  # (U, Rmax) tgt>0 bitmask (0 = no test; folds canti)
    gid_u: np.ndarray  # (U,)
    self_ok_u: np.ndarray  # (U,) match_all[gid, u]
    slot_grows: np.ndarray  # (U, Gmax) A-row idx
    h_topo: np.ndarray  # (U, Hmax) topo_dist idx (-1 = inactive)
    h_cnt: np.ndarray  # (U, Hmax) tgt_cnt idx
    h_cand: np.ndarray  # (U, Hmax) cand_dist idx
    h_skew: np.ndarray  # (U, Hmax) max skew
    h_selfm: np.ndarray  # (U, Hmax) h_self[h, u]
    s_topo_i: np.ndarray  # (U, Smax) topo_dist idx (-1 = inactive)
    s_ishost: np.ndarray  # (U, Smax)
    s_cnt: np.ndarray  # (U, Smax) tgt_cnt idx (host rows; -1 otherwise)
    s_nh: np.ndarray  # (U, Smax) soft_nh idx (non-host; -1 otherwise)
    s_skewm1: np.ndarray  # (U, Smax) max_skew - 1 (prefolded)
    # --- SMEM commit slot tables (U, Cmax) ---------------------------
    c_topo: np.ndarray  # topo_dist idx (-1 = inactive slot)
    c_cnt: np.ndarray  # tgt_cnt idx (-1 = no count update)
    c_pref: np.ndarray  # pref idx (-1 = no pref update)
    c_m: np.ndarray  # match increment
    c_prefc: np.ndarray  # combined preferred commit increment
    c_pantic: np.ndarray  # anti-preferred commit increment
    c_antip: np.ndarray  # anti plane idx
    c_antib: np.ndarray  # anti bitmask (0 = no bit set)
    c_tposp: np.ndarray  # tgt>0 plane idx
    c_tposb: np.ndarray  # tgt>0 bitmask (0 = no bit set)
    # --- SMEM non-host soft commit slots (U, SCmax) ------------------
    sc_nh: np.ndarray  # soft_nh idx (-1 = inactive)
    sc_topo: np.ndarray  # topo_dist idx
    sc_q: np.ndarray  # sq_dist idx
    sc_m: np.ndarray  # match increment
    # f64 log-weight tables split for double-single arithmetic:
    # w = log(sz+2) computed in f64 on host; hi/lo f32 split, hi further
    # split into 12-bit halves h1+h2 for exact f32 products. (Wr, 128)
    # f32 VMEM tiles — the tables are node-count sized (sz ranges
    # 0..n+1), so SMEM placement capped term plans at ~50k nodes; the
    # kernel reads them by dynamic sublane row + lane mask (wval)
    w_hi: np.ndarray  # (Wr, 128) f32
    w_lo: np.ndarray
    w_h1: np.ndarray
    w_h2: np.ndarray


# the device dry run's slot fields (ops/preempt.py), in table order
_PRE_FIELDS = ("valid", "hard", "prio", "seq", "mcpu", "mem", "eph", "nz_mcpu", "nz_mem")
# slots per node the kernel's unrolled dry run holds (K^2 selects per
# field); a batch needing more runs its dry run on the XLA scan
_PRE_MAX_K = 16


class PreemptPlan(NamedTuple):
    """DefaultPreemption's dry run in the kernel (_kernel_dry_run): the
    committed pods as (F*K, R, C) int32 tiles, field f of slot k at
    f*K + k (_PRE_FIELDS order, memory in the plan's scaled units), the
    per-pod (3, Pr, C) rows priority / dry run allowed / out of scope
    once committed, and the next commit sequence."""

    k: int
    table0: np.ndarray  # (F*K, R, C) init slots (ANY)
    pod: np.ndarray  # (3, Pr, C) VMEM
    meta: np.ndarray  # (1,) SMEM next commit sequence


class PallasPlan(NamedTuple):
    """Host-side (numpy) arrays prepared for the kernel, all padded to
    (R, 128) node tiles / int32."""

    n: int  # true node count
    r: int  # padded rows (multiple of 8)
    u: int  # class count
    # [R, C] node vectors
    alloc_mcpu: np.ndarray
    alloc_mem_s: np.ndarray  # fit-scaled
    alloc_eph_s: np.ndarray
    alloc_pods: np.ndarray
    alloc_nzmem_s: np.ndarray  # nz-scaled (balanced/least denominator)
    # class tables, deduplicated to distinct rows; clsmap (SMEM) maps
    # class u -> row per table: 0=feas 1=simon 2=base 3=nodeaff 4=taint
    # 5=haskeys (terms) 6/7 spare
    static_feasible: np.ndarray  # (Fd, R, C)
    simon_raw: np.ndarray  # (Sd, R, C)
    nodeaff_raw: np.ndarray  # (Nad, R, C)
    taint_intol: np.ndarray  # (Ttd, R, C)
    base_score: np.ndarray  # (Bd, R, C) prefolded image*w_image + avoid*w_avoid
    clsmap: np.ndarray  # (8, Up) i32
    # [U, 8] class scalars: req_mcpu, req_mem_s, req_eph_s, nz_mcpu,
    # nz_mem_s, has_request, 0, 0
    class_scalars: np.ndarray
    # init state [R, C] i32 x6
    init_used_mcpu: np.ndarray
    init_used_mem_s: np.ndarray
    init_used_eph_s: np.ndarray
    init_nz_mcpu: np.ndarray
    init_nz_mem_s: np.ndarray
    init_pod_cnt: np.ndarray
    # scales to recover true units
    s_mem: int
    s_eph: int
    s_nzmem: int
    # weights (least, balanced, simon+gpushare, nodeaff, tainttol,
    # spread, ipa)
    w: tuple
    has_nodeaff: bool
    has_taint: bool
    has_pins: bool  # any pod arrives with spec.nodeName
    # inter-pod affinity / topology-spread machinery (None = batch has
    # no terms)
    terms: Optional[TermsPlan]
    # extended scalar resources (noderesources/fit.go scalar path):
    # s_n resource kinds, per-kind GCD-scaled int32
    s_n: int = 0
    alloc_scal: Optional[np.ndarray] = None  # (S, R, C) VMEM
    iscal0: Optional[np.ndarray] = None  # (S, R, C) init used (ANY)
    req_scal: Optional[np.ndarray] = None  # (U*S,) SMEM
    # hostPorts (NodePorts plugin): occupancy as pw int32 bitplanes
    # over the port vocab, conflict/want masks as per-class words
    pw: int = 0
    ports0: Optional[np.ndarray] = None  # (Pw, R, C) init planes (ANY)
    want_w: Optional[np.ndarray] = None  # (U*Pw,) SMEM
    confl_w: Optional[np.ndarray] = None  # (U*Pw,) SMEM
    # open-gpu-share: g_n devices per node, memory in GCD-scaled int32
    g_n: int = 0
    gpu_per_dev: Optional[np.ndarray] = None  # (R, C) VMEM
    gpu_cnt_n: Optional[np.ndarray] = None  # (R, C) VMEM device counts
    gpu_tot: Optional[np.ndarray] = None  # (R, C) VMEM capacity gpu-mem
    igpu0: Optional[np.ndarray] = None  # (G, R, C) init used (ANY)
    gpu_mem_u: Optional[np.ndarray] = None  # (U,) SMEM per-GPU request
    gpu_cnt_u: Optional[np.ndarray] = None  # (U,) SMEM device count
    # open-local storage: VG binpack + exclusive-device fit in GCD-
    # scaled int32; the f64 ScoreLVM/ScoreDevice values ride as host-
    # precomputed SMEM tables indexed by (class, distinct node storage
    # config, in-kernel assignment pattern) — see _build_storage
    store: Optional["StorePlan"] = None
    # the device dry run (ops/preempt.py); None = no preemption
    pre: Optional[PreemptPlan] = None


def _pad_nodes(vec: np.ndarray, r: int, fill=0) -> np.ndarray:
    out = np.full(r * LANES, fill, dtype=np.int32)
    out[: vec.shape[0]] = vec
    return out.reshape(r, LANES)


def _pad_class_table(tab: np.ndarray, r: int, fill=0) -> np.ndarray:
    u, n = tab.shape
    out = np.full((u, r * LANES), fill, dtype=np.int32)
    out[:, :n] = tab
    return out.reshape(u, r, LANES)


def _gcd_scale(*arrays) -> int:
    vals = np.concatenate([np.asarray(a, dtype=np.int64).ravel() for a in arrays])
    vals = vals[vals > 0]
    if vals.size == 0:
        return 1
    return int(np.gcd.reduce(vals))


def _pad_lanes(vec: np.ndarray, dtype=np.int32, fill=0) -> np.ndarray:
    """1-D vector -> (8, Lp) tile, data in row 0."""
    lp = max(-(-vec.shape[0] // LANES) * LANES, LANES)
    out = np.full((SUBLANES, lp), fill, dtype=dtype)
    out[0, : vec.shape[0]] = vec
    return out


def _pad_table(tab: np.ndarray, fill=0, dtype=np.int32) -> np.ndarray:
    """(X, Y) table -> (Xp, Yp) with sublane/lane padding."""
    x, y = tab.shape
    xp = max(-(-x // SUBLANES) * SUBLANES, SUBLANES)
    yp = max(-(-y // LANES) * LANES, LANES)
    out = np.full((xp, yp), fill, dtype=dtype)
    out[:x, :y] = tab
    return out


def _pad_stack(tab: np.ndarray, r: int, fill=0) -> np.ndarray:
    """(X, N) node table -> (Xp, R, C) i32 node tiles."""
    x, n = tab.shape
    xp = max(x, 1)
    out = np.full((xp, r * LANES), fill, dtype=np.int32)
    out[:x, :n] = tab
    return out.reshape(xp, r, LANES)


# slot-count caps keep the kernel's static unrolled loops small; a batch
# beyond them falls back to the XLA scan
_MAX_SLOTS = dict(rmax=8, gmax=4, hmax=4, smax=4, a=8, gn=8, vs=32,
                  cmax=8, scmax=4, kmax=64, wmax=32)
# DMA semaphores the streamed-terms gather round-robins over: enough to
# keep a pod step's row fetches in flight concurrently without paying a
# serialized wait per row
_STREAM_NSEM = 8
_MAX_COUNT = 1 << 17  # cnt exact-split bound for the soft f64 emulation
_MAX_T = 512
# pod classes the term kernel accepts: class-column tables span
# ceil(U/128) sublane rows (col_u reads one dynamically); the cap
# bounds their VMEM rows and the U-strided SMEM slot tables
_MAX_U = 4 * LANES
# total int32 entries across the SMEM-destined term tables (~1MB SMEM
# per core; stay well under it so Mosaic never fails at compile time)
_MAX_SMEM_ENTRIES = 200_000


def _dedup_rows(tab: np.ndarray):
    """(X, N) -> (distinct (D, N), idx[X]) by row content."""
    if tab.shape[0] == 0:
        return tab.reshape(0, tab.shape[1]), np.zeros(0, dtype=np.int32)
    seen: dict = {}
    idx = np.zeros(tab.shape[0], dtype=np.int32)
    rows = []
    for i in range(tab.shape[0]):
        key = tab[i].tobytes()
        j = seen.get(key)
        if j is None:
            j = len(rows)
            seen[key] = j
            rows.append(tab[i])
        idx[i] = j
    return np.stack(rows), idx


# why the most recent build_plan returned None — the engine copies it
# into the `batch-kernel` trace note so a fast-path fallback is never
# silent (VERDICT r2 weak #3 observability)
_LAST_REJECT: Optional[str] = None


def last_reject() -> Optional[str]:
    return _LAST_REJECT


def fallback_reason() -> str:
    """The trace-note suffix for a plan==None outcome, read immediately
    after a build_plan call — shared by every consumer so no fast-path
    fallback is ever noted without its reason."""
    if not should_use():
        return "no TPU backend"
    return _LAST_REJECT or "rejected"


def _reject(reason: str) -> None:
    global _LAST_REJECT
    _LAST_REJECT = reason
    return None


def _pr_rows(p_total: int) -> int:
    """Rows of the dense (Pr, 128) placement packing — the one
    definition shared by run_scan_pallas (output allocation) and
    decode_scan_output (row split); they must agree or the split lands
    mid-block."""
    rows = max(-(-p_total // LANES), 1)
    return -(-rows // SUBLANES) * SUBLANES


def _bit(r: int) -> int:
    """int32 bitmask for logical row r (bit r & 31 of plane r >> 5)."""
    return int(np.uint32(1 << (r & 31)).view(np.int32))


def _pack_bitplanes(mask_tn: np.ndarray) -> np.ndarray:
    """(T, N) bool -> (ceil(T/32), N) int32 planes, row r at bit r&31
    of plane r>>5."""
    t_rows, n_cols = mask_tn.shape
    bp = max(-(-t_rows // 32), 1)
    planes = np.zeros((bp, n_cols), dtype=np.uint32)
    for r_i in range(t_rows):
        planes[r_i >> 5] |= mask_tn[r_i].astype(np.uint32) << np.uint32(r_i & 31)
    return planes.view(np.int32)


def _build_terms(batch, features, r: int, p_total: int, n: int):
    """Term-machinery plan (see TermsPlan docstring for the memory
    design) plus the per-class haskeys map, or None when out of the
    kernel's scope."""
    t = batch.terms
    has_ipa = bool(features.ipa)
    has_hard = bool(features.hard_spread)
    has_soft = bool(features.soft_spread)

    if t.t > _MAX_T or t.rmax > _MAX_SLOTS["rmax"] or t.gmax > _MAX_SLOTS["gmax"]:
        return _reject("terms: instance/slot count over kernel bounds")
    if t.hmax > _MAX_SLOTS["hmax"] or t.smax > _MAX_SLOTS["smax"]:
        return _reject("terms: spread slot count over kernel bounds")
    if t.a > _MAX_SLOTS["a"] or len(t.match_all) > _MAX_SLOTS["gn"]:
        return _reject("terms: affinity-group count over kernel bounds")
    if batch.u > _MAX_U:
        # class-indexed lane tables span ceil(U/128) sublane rows; the
        # cap bounds their VMEM rows and the SMEM slot tables
        return _reject(f"terms: {batch.u} pod classes > {_MAX_U}-class scope")

    from .encode import _value_to_node_space
    from .terms import combined_pref_carry, combined_pref_init

    tv = t.topo_val
    u_n = batch.u
    carry_prefc = combined_pref_carry(t)
    pref_init = combined_pref_init(t)

    # int32 exactness bounds (documented in the module docstring)
    tgt0_all = _value_to_node_space(t.init_tgt, tv)
    pref0_all = _value_to_node_space(pref_init, tv)
    panti0_all = _value_to_node_space(t.init_own_anti_pref_w, tv)
    cnt_max = int(tgt0_all.max(initial=0)) + p_total
    pref_max = int(
        max(pref0_all.max(initial=0), panti0_all.max(initial=0))
    ) + p_total * int(
        max(np.abs(carry_prefc).max(initial=0), np.abs(t.carry_anti_pref_w).max(initial=0), 1)
    )
    ipa_raw_max = t.rmax * (
        int(
            (np.abs(t.carry_aff_pref_w) + np.abs(t.carry_anti_pref_w)).max(initial=0)
        )
        * cnt_max
        + 2 * pref_max
    )
    if cnt_max > _MAX_COUNT or pref_max > 2**30 or ipa_raw_max > 2**23:
        return _reject("terms: count/weight magnitudes exceed int32 exactness")

    # soft vocab for the distinct-domain loop
    vs = 1
    if has_soft:
        nonhost = ~t.s_is_host
        real = (t.cls_s_rows >= 0).any()
        if real and nonhost.any():
            mx = int(tv[t.s_row][nonhost].max(initial=-1))
            vs = max(mx + 1, 1)
        if vs > _MAX_SLOTS["vs"]:
            return _reject("terms: soft-spread domain vocab over kernel bound")

    # -- row storage classification ----------------------------------
    # count rows: some consumer reads them as COUNTS — score carries
    # (cpd != 0), hard-spread instances, host-topology soft instances.
    # pref rows: any preferred-weight data (init or carry).
    # Everything else is tested only as `> 0` and lives in bitplanes.
    cpd_tu = (t.carry_aff_pref_w - t.carry_anti_pref_w).astype(np.int64)
    cnt_need = np.zeros(t.t, dtype=bool)
    cnt_need[np.nonzero((cpd_tu != 0).any(axis=1))[0]] = True
    if has_hard:
        used_h = np.unique(t.cls_h_rows[t.cls_h_rows >= 0])
        cnt_need[t.h_row[used_h]] = True
    if has_soft:
        used_s = np.unique(t.cls_s_rows[t.cls_s_rows >= 0])
        host_s = used_s[t.s_is_host[used_s]]
        cnt_need[t.s_row[host_s]] = True
    pref_need = (
        (pref_init != 0).any(axis=1)
        | (t.init_own_anti_pref_w != 0).any(axis=1)
        | (carry_prefc != 0).any(axis=1)
        | (t.carry_anti_pref_w != 0).any(axis=1)
    )
    cnt_idx = np.full(t.t, -1, dtype=np.int32)
    cnt_rows = np.nonzero(cnt_need)[0]
    cnt_idx[cnt_rows] = np.arange(len(cnt_rows))
    pref_idx = np.full(t.t, -1, dtype=np.int32)
    pref_rows = np.nonzero(pref_need)[0]
    pref_idx[pref_rows] = np.arange(len(pref_rows))
    tc_n = max(len(cnt_rows), 1)
    tp_n = max(len(pref_rows), 1)
    bp_n = max(-(-t.t // 32), 1)

    # early VMEM pre-gate: the scratch state alone is a lower bound on
    # the final tile count (build_plan re-checks exactly); rejecting
    # here skips the O(U*T) slot-table construction for hopeless plans.
    # Only binding when streaming is disabled — a streamed plan keeps
    # this state in HBM, so over-budget scratch is exactly the case
    # build_plan's streaming rewrite exists for.
    scratch_tiles = tc_n + 2 * tp_n + 2 * bp_n + t.a
    if scratch_tiles * r * LANES * 4 > 13 * 2**20 and STREAM_FORCE is False:
        return _reject("terms: scratch state exceeds VMEM budget")

    # -- static dedup --------------------------------------------------
    topo_dist, topo_idx = _dedup_rows(tv)
    td_n = topo_dist.shape[0]
    cand_dist, cand_idx = _dedup_rows(t.h_cand_nodes.astype(np.int32))
    cd_n = max(cand_dist.shape[0], 1)
    hk_dist, hk_map = _dedup_rows(t.cls_s_haskeys.astype(np.int32))
    hkd_n = max(hk_dist.shape[0], 1)

    # -- non-host soft instances --------------------------------------
    nh_mask = ~t.s_is_host
    nh_insts = np.nonzero(nh_mask)[0]
    nh_idx = np.full(t.cs, -1, dtype=np.int32)
    nh_idx[nh_insts] = np.arange(len(nh_insts))
    csn_n = max(len(nh_insts), 1)
    if len(nh_insts):
        sq_dist, sq_idx_nh = _dedup_rows(t.s_q[nh_insts].astype(np.int32))
        sq_idx = np.full(t.cs, -1, dtype=np.int32)
        sq_idx[nh_insts] = sq_idx_nh
        soft0_nh = _value_to_node_space(
            t.init_soft_counts[nh_insts], tv[t.s_row[nh_insts]]
        )
    else:
        sq_dist = np.zeros((1, n), dtype=np.int32)
        sq_idx = np.full(t.cs, -1, dtype=np.int32)
        soft0_nh = np.zeros((1, n), dtype=np.int64)
    sqd_n = max(sq_dist.shape[0], 1)

    # -- eval slot tables (resolved storage indices) -------------------
    rmax = t.rmax
    e_cnt = np.full((u_n, rmax), -1, dtype=np.int32)
    e_pref = np.full((u_n, rmax), -1, dtype=np.int32)
    e_cpd = np.zeros((u_n, rmax), dtype=np.int64)
    e_antip = np.zeros((u_n, rmax), dtype=np.int32)
    e_antib = np.zeros((u_n, rmax), dtype=np.int32)
    e_tposp = np.zeros((u_n, rmax), dtype=np.int32)
    e_tposb = np.zeros((u_n, rmax), dtype=np.int32)
    for u_i in range(u_n):
        for k in range(rmax):
            row = int(t.cls_rows[u_i, k])
            if row < 0:
                continue
            cpd = int(cpd_tu[row, u_i])
            e_cpd[u_i, k] = cpd
            if cpd != 0:
                e_cnt[u_i, k] = cnt_idx[row]
            m_k = bool(t.match[row, u_i])
            if m_k and pref_idx[row] >= 0:
                e_pref[u_i, k] = pref_idx[row]
            e_antip[u_i, k] = row >> 5
            e_tposp[u_i, k] = row >> 5
            if m_k:
                e_antib[u_i, k] = _bit(row)
            if int(t.carry_anti_req[row, u_i]) > 0:
                e_tposb[u_i, k] = _bit(row)

    # -- commit slot tables --------------------------------------------
    # bit updates are emitted only for rows some class actually tests:
    # fail_exist tests anti bits on matched rows, fail_own tests tgt>0
    # bits on rows the class carries required anti-affinity for
    tested_exist = t.match.any(axis=1)
    tested_own = (t.carry_anti_req > 0).any(axis=1)
    commit_slots: list = [[] for _ in range(u_n)]
    for u_i in range(u_n):
        for row in range(t.t):
            m_i = int(t.match[row, u_i])
            prefc = int(carry_prefc[row, u_i])
            pantic = int(t.carry_anti_pref_w[row, u_i])
            canti = int(t.carry_anti_req[row, u_i])
            upd_cnt = bool(m_i) and cnt_idx[row] >= 0
            upd_pref = (prefc != 0 or pantic != 0) and pref_idx[row] >= 0
            upd_anti = canti > 0 and bool(tested_exist[row])
            upd_tpos = bool(m_i) and bool(tested_own[row])
            if not (upd_cnt or upd_pref or upd_anti or upd_tpos):
                continue
            commit_slots[u_i].append(
                dict(
                    topo=int(topo_idx[row]),
                    cnt=int(cnt_idx[row]) if upd_cnt else -1,
                    pref=int(pref_idx[row]) if upd_pref else -1,
                    m=m_i,
                    prefc=prefc,
                    pantic=pantic,
                    antip=row >> 5,
                    antib=_bit(row) if upd_anti else 0,
                    tposp=row >> 5,
                    tposb=_bit(row) if upd_tpos else 0,
                )
            )
    cmax = max((len(s) for s in commit_slots), default=0)
    cmax = max(cmax, 1)
    if cmax > _MAX_SLOTS["cmax"]:
        return _reject("terms: per-class commit slots over kernel bound")
    c_topo = np.full((u_n, cmax), -1, dtype=np.int32)
    c_cnt = np.full((u_n, cmax), -1, dtype=np.int32)
    c_pref = np.full((u_n, cmax), -1, dtype=np.int32)
    c_m = np.zeros((u_n, cmax), dtype=np.int32)
    c_prefc = np.zeros((u_n, cmax), dtype=np.int32)
    c_pantic = np.zeros((u_n, cmax), dtype=np.int32)
    c_antip = np.zeros((u_n, cmax), dtype=np.int32)
    c_antib = np.zeros((u_n, cmax), dtype=np.int32)
    c_tposp = np.zeros((u_n, cmax), dtype=np.int32)
    c_tposb = np.zeros((u_n, cmax), dtype=np.int32)
    for u_i, slots in enumerate(commit_slots):
        for j, s in enumerate(slots):
            c_topo[u_i, j] = s["topo"]
            c_cnt[u_i, j] = s["cnt"]
            c_pref[u_i, j] = s["pref"]
            c_m[u_i, j] = s["m"]
            c_prefc[u_i, j] = s["prefc"]
            c_pantic[u_i, j] = s["pantic"]
            c_antip[u_i, j] = s["antip"]
            c_antib[u_i, j] = s["antib"]
            c_tposp[u_i, j] = s["tposp"]
            c_tposb[u_i, j] = s["tposb"]

    # non-host soft commit slots
    sc_slots: list = [[] for _ in range(u_n)]
    if has_soft and len(nh_insts):
        for u_i in range(u_n):
            for inst in nh_insts:
                row = int(t.s_row[inst])
                if not t.match[row, u_i]:
                    continue
                sc_slots[u_i].append(
                    dict(nh=int(nh_idx[inst]), topo=int(topo_idx[row]),
                         q=int(sq_idx[inst]), m=1)
                )
    scmax = max((len(s) for s in sc_slots), default=0)
    scmax = max(scmax, 1)
    if scmax > _MAX_SLOTS["scmax"]:
        return _reject("terms: per-class score slots over kernel bound")
    sc_nh = np.full((u_n, scmax), -1, dtype=np.int32)
    sc_topo = np.zeros((u_n, scmax), dtype=np.int32)
    sc_q = np.zeros((u_n, scmax), dtype=np.int32)
    sc_m = np.zeros((u_n, scmax), dtype=np.int32)
    for u_i, slots in enumerate(sc_slots):
        for j, s in enumerate(slots):
            sc_nh[u_i, j] = s["nh"]
            sc_topo[u_i, j] = s["topo"]
            sc_q[u_i, j] = s["q"]
            sc_m[u_i, j] = s["m"]

    # -- hard / soft eval tables (resolved) ---------------------------
    hmax, smax = t.hmax, t.smax
    h_topo = np.full((u_n, hmax), -1, dtype=np.int32)
    h_cnt = np.zeros((u_n, hmax), dtype=np.int32)
    h_cand = np.zeros((u_n, hmax), dtype=np.int32)
    h_skew = np.zeros((u_n, hmax), dtype=np.int32)
    h_selfm = np.zeros((u_n, hmax), dtype=np.int32)
    for u_i in range(u_n):
        for k in range(hmax):
            inst = int(t.cls_h_rows[u_i, k])
            if inst < 0:
                continue
            row = int(t.h_row[inst])
            h_topo[u_i, k] = topo_idx[row]
            h_cnt[u_i, k] = cnt_idx[row]
            h_cand[u_i, k] = cand_idx[inst]
            h_skew[u_i, k] = int(t.h_max_skew[inst])
            h_selfm[u_i, k] = int(t.h_self[inst, u_i])
    s_topo_i = np.full((u_n, smax), -1, dtype=np.int32)
    s_ishost = np.zeros((u_n, smax), dtype=np.int32)
    s_cnt = np.full((u_n, smax), -1, dtype=np.int32)
    s_nh = np.full((u_n, smax), -1, dtype=np.int32)
    s_skewm1 = np.zeros((u_n, smax), dtype=np.int32)
    for u_i in range(u_n):
        for k in range(smax):
            inst = int(t.cls_s_rows[u_i, k])
            if inst < 0:
                continue
            row = int(t.s_row[inst])
            s_topo_i[u_i, k] = topo_idx[row]
            s_ishost[u_i, k] = int(t.s_is_host[inst])
            if t.s_is_host[inst]:
                s_cnt[u_i, k] = cnt_idx[row]
            else:
                s_nh[u_i, k] = nh_idx[inst]
            s_skewm1[u_i, k] = int(t.s_max_skew[inst]) - 1

    # -- state inits (node space, trimmed to stored rows) --------------
    tgt0_c = tgt0_all[cnt_rows] if len(cnt_rows) else np.zeros((1, n), np.int64)
    pref0_p = pref0_all[pref_rows] if len(pref_rows) else np.zeros((1, n), np.int64)
    panti0_p = panti0_all[pref_rows] if len(pref_rows) else np.zeros((1, n), np.int64)
    anti0_all = _value_to_node_space(t.init_own_anti_req, tv)
    antib0 = _pack_bitplanes(anti0_all > 0)
    tposb0 = _pack_bitplanes(tgt0_all > 0)
    group0 = _value_to_node_space(t.init_group_counts, tv[t.group_rows])

    # f64 log weights, double-single split (sz ranges over 0..n+1) —
    # node-count sized, so they live as (Wr, 128) VMEM tiles read by
    # dynamic sublane row (SMEM placement capped plans at ~50k nodes);
    # soft-free batches carry a 1-row dummy
    wn = n + 2 if has_soft else 1
    szv = np.arange(wn, dtype=np.float64)
    w64 = np.log(szv + 2.0)
    w_hi = w64.astype(np.float32)
    w_lo = (w64 - w_hi.astype(np.float64)).astype(np.float32)
    # 12-bit split of w_hi for exact f32 products with cnt <= 2^17
    scale = np.float32(2**12 + 1)
    tmp = w_hi * scale
    w_h1 = (tmp - (tmp - w_hi)).astype(np.float32)  # Veltkamp split
    w_h2 = (w_hi - w_h1).astype(np.float32)

    def wpack(v: np.ndarray) -> np.ndarray:
        r_w = -(-v.shape[0] // LANES)
        r_w = -(-r_w // SUBLANES) * SUBLANES
        out = np.zeros(r_w * LANES, dtype=np.float32)
        out[: v.shape[0]] = v
        return out.reshape(r_w, LANES)

    # class-column tables: ceil(U/128) sublane rows of 128 lanes each,
    # padded to the (8, 128) tile grain; the kernel's col_u selects row
    # u//128 dynamically and lane u%128 by mask
    u_rows = -(-max(u_n, 1) // LANES)
    u_rows_p = -(-u_rows // SUBLANES) * SUBLANES

    def tab_u(m, dtype=np.int32):
        """(X, U) -> (X, Ur_p, 128) class-column tile."""
        x = max(m.shape[0], 1)
        out = np.zeros((x, u_rows_p * LANES), dtype=dtype)
        out[: m.shape[0], : m.shape[1]] = m
        return out.reshape(x, u_rows_p, LANES)

    gid_u = t.cls_group_id.astype(np.int32)
    uu = np.arange(u_n)
    self_ok_u = np.where(
        gid_u >= 0, t.match_all[np.maximum(gid_u, 0), uu], False
    )

    cfg = TermsCfg(
        t=t.t, td=td_n, tc=tc_n, tp=tp_n, bp=bp_n, a=t.a,
        gn=len(t.match_all), csn=csn_n, cd=cd_n, sqd=sqd_n, hkd=hkd_n,
        rmax=rmax, gmax=t.gmax, hmax=hmax, smax=smax, cmax=cmax,
        scmax=scmax, vs=vs,
        has_ipa=has_ipa, has_hard=has_hard, has_soft=has_soft,
    )
    plan = TermsPlan(
        cfg=cfg,
        topo_dist=_pad_stack(topo_dist, r, fill=-1),
        g_topo3=_pad_stack(tv[t.group_rows], r, fill=-1),
        cand_dist=_pad_stack(cand_dist, r),
        sq_dist=_pad_stack(sq_dist, r),
        hk_dist=_pad_stack(hk_dist, r),
        g_match_au=tab_u(t.match_all[t.group_of_row].astype(np.int32)),
        tgt0_c=_pad_stack(tgt0_c, r),
        pref0_p=_pad_stack(pref0_p, r),
        panti0_p=_pad_stack(panti0_p, r),
        antib0=_pad_stack(antib0, r),
        tposb0=_pad_stack(tposb0, r),
        group0=_pad_stack(group0, r),
        gtot0=np.ascontiguousarray(
            np.broadcast_to(
                t.init_group_counts.sum(axis=1).astype(np.int32)[:, None, None],
                (max(t.a, 1), SUBLANES, LANES),
            )
        ),
        soft0_nh=_pad_stack(soft0_nh, r),
        # (U, slot) tables ship FLATTENED 1-D: SMEM pads every row of a
        # 2-D array to a full 512B lane-row, so (100, 3) would cost
        # 51KB of the ~1MB SMEM; 1-D costs its actual bytes
        e_cnt=e_cnt.reshape(-1), e_pref=e_pref.reshape(-1),
        e_cpd=e_cpd.astype(np.int32).reshape(-1),
        e_antip=e_antip.reshape(-1), e_antib=e_antib.reshape(-1),
        e_tposp=e_tposp.reshape(-1), e_tposb=e_tposb.reshape(-1),
        gid_u=gid_u,
        self_ok_u=self_ok_u.astype(np.int32),
        slot_grows=t.cls_group_rows.astype(np.int32).reshape(-1),
        h_topo=h_topo.reshape(-1), h_cnt=h_cnt.reshape(-1),
        h_cand=h_cand.reshape(-1), h_skew=h_skew.reshape(-1),
        h_selfm=h_selfm.reshape(-1),
        s_topo_i=s_topo_i.reshape(-1), s_ishost=s_ishost.reshape(-1),
        s_cnt=s_cnt.reshape(-1), s_nh=s_nh.reshape(-1),
        s_skewm1=s_skewm1.reshape(-1),
        c_topo=c_topo.reshape(-1), c_cnt=c_cnt.reshape(-1),
        c_pref=c_pref.reshape(-1), c_m=c_m.reshape(-1),
        c_prefc=c_prefc.reshape(-1), c_pantic=c_pantic.reshape(-1),
        c_antip=c_antip.reshape(-1), c_antib=c_antib.reshape(-1),
        c_tposp=c_tposp.reshape(-1), c_tposb=c_tposb.reshape(-1),
        sc_nh=sc_nh.reshape(-1), sc_topo=sc_topo.reshape(-1),
        sc_q=sc_q.reshape(-1), sc_m=sc_m.reshape(-1),
        w_hi=wpack(w_hi),
        w_lo=wpack(w_lo),
        w_h1=wpack(w_h1),
        w_h2=wpack(w_h2),
    )
    smem_entries = sum(
        getattr(plan, name).size
        for name, space in _TERM_FIELDS
        if space == "smem"
    )
    if smem_entries > _MAX_SMEM_ENTRIES:
        # reject here rather than let Mosaic fail at compile time —
        # the caller falls back to the XLA scan
        return _reject(
            f"terms: {smem_entries} SMEM slot-table entries over budget"
        )
    return plan, hk_map


# the term-machinery kernel beats the XLA scan on term-heavy batches
# (measured before the current chip; on by default, opt out for
# debugging)
TERMS_DEFAULT_ENABLE = True

# streamed-terms routing: None = auto (stream only when the resident
# term state exceeds the VMEM budget), True = force streaming for any
# terms batch (conformance tests / bench A/B), False = never stream
# (resident-or-XLA, the r4 behavior)
STREAM_FORCE: Optional[bool] = None


def build_plan(cluster, batch, dyn, features, weights=None,
               allow_terms: Optional[bool] = None,
               preempt=None) -> Optional[PallasPlan]:
    """Build a kernel plan from the (numpy) ClusterStatic + PodBatch +
    DynamicState, or None when the batch is outside the fast path's
    scope. With features.preempt, `preempt` is (ops/preempt.table_np
    slots, next commit sequence, per-pod priority, dry run allowed, out
    of scope once committed)."""
    if getattr(features, "preempt", False) and preempt is None:
        return _reject("preemption dry run without its slots")
    if features.custom:
        return _reject("custom-plugin machinery (XLA scan carries it)")
    if getattr(features, "sample", False):
        return _reject(
            "sample-mode selectHost (XLA scan carries the Go RNG)"
        )
    if features.gpu and features.pins:
        # forced gpu commits would need device allocation outside the
        # feasibility gate; rare combination, XLA scan carries it
        return _reject("gpu batch with nodeName pins")
    if allow_terms is None:
        allow_terms = TERMS_DEFAULT_ENABLE
    if not allow_terms and (
        features.ipa or features.hard_spread or features.soft_spread
    ):
        return _reject("terms disabled (allow_terms=False)")

    from ..scheduler.schedconfig import DEFAULT_SCORE_WEIGHTS, ScoreWeights

    w = ScoreWeights(*weights) if weights is not None else DEFAULT_SCORE_WEIGHTS

    a = np.asarray
    alloc_mcpu = a(cluster.alloc_mcpu, dtype=np.int64)
    alloc_mem = a(cluster.alloc_mem, dtype=np.int64)
    alloc_eph = a(cluster.alloc_eph, dtype=np.int64)
    alloc_pods = a(cluster.alloc_pods, dtype=np.int64)
    req_mcpu = a(batch.req_mcpu, dtype=np.int64)
    req_mem = a(batch.req_mem, dtype=np.int64)
    req_eph = a(batch.req_eph, dtype=np.int64)
    nz_mcpu = a(batch.nz_mcpu, dtype=np.int64)
    nz_mem = a(batch.nz_mem, dtype=np.int64)
    init_used_mcpu = a(dyn.used_mcpu, dtype=np.int64)
    init_used_mem = a(dyn.used_mem, dtype=np.int64)
    init_used_eph = a(dyn.used_eph, dtype=np.int64)
    init_nz_mcpu = a(dyn.nz_mcpu, dtype=np.int64)
    init_nz_mem = a(dyn.nz_mem, dtype=np.int64)
    init_pod_cnt = a(dyn.pod_cnt, dtype=np.int64)

    # a preemption releases single pods' requests: the scales divide
    # every committed pod's too
    pre_tab = preempt[0] if preempt is not None else {}
    s_mem = _gcd_scale(alloc_mem, req_mem, init_used_mem, pre_tab.get("mem", ()))
    s_eph = _gcd_scale(alloc_eph, req_eph, init_used_eph, pre_tab.get("eph", ()))
    s_nzmem = _gcd_scale(alloc_mem, nz_mem, init_nz_mem, pre_tab.get("nz_mem", ()))

    simon_raw = a(batch.simon_raw, dtype=np.int64)
    nodeaff_raw = a(batch.nodeaff_raw, dtype=np.int64)
    taint_intol = a(batch.taint_intol, dtype=np.int64)
    image_score = a(batch.image_score, dtype=np.int64)
    avoid_score = a(batch.avoid_score, dtype=np.int64)
    base_score = image_score * int(w.image) + avoid_score * int(w.avoid)

    # int32 exactness guards
    checks = [
        alloc_mcpu.max(initial=0) <= _MAX_SCALED,
        (alloc_mem // s_mem).max(initial=0) <= _MAX_SCALED,
        (alloc_eph // s_eph).max(initial=0) <= _MAX_SCALED,
        (alloc_mem // s_nzmem).max(initial=0) <= _MAX_SCALED,
        alloc_pods.max(initial=0) <= _MAX_SCALED,
        simon_raw.max(initial=0) <= _MAX_SCALED,
        simon_raw.min(initial=0) >= 0,
        nodeaff_raw.max(initial=0) <= _MAX_SCALED,
        nodeaff_raw.min(initial=0) >= 0,
        taint_intol.max(initial=0) <= _MAX_SCALED,
        taint_intol.min(initial=0) >= 0,
        np.abs(base_score).max(initial=0) <= 2**24,
        # balanced runs in f32: its scaled inputs must be f32-exact
        (alloc_mem // s_nzmem).max(initial=0) < 2**24,
        alloc_mcpu.max(initial=0) < 2**24,
    ]
    if not all(bool(c) for c in checks):
        return _reject("resource/score magnitudes exceed int32/f32 exactness")

    n = alloc_mcpu.shape[0]
    u = req_mcpu.shape[0]
    r = -(-n // LANES)
    r = -(-r // SUBLANES) * SUBLANES  # row count multiple of 8

    if features.pins:
        # forced pin commits bypass the feasibility gate, so per-node
        # usage is no longer bounded by alloc: bound each node's usage
        # with every pod pinned to it against the f32/int32 guards
        pinned_node = a(batch.pinned_node)
        pin_mask = pinned_node >= 0
        pin_at = pinned_node[pin_mask]
        pin_cls = a(batch.class_of_pod)[pin_mask]

        def with_pins(init, per_class):
            used = np.array(init, dtype=np.int64)
            np.add.at(used, pin_at, per_class[pin_cls])
            return int(used.max(initial=0))

        worst = max(
            with_pins(init_used_mcpu, req_mcpu),
            with_pins(init_used_mem // s_mem, req_mem // s_mem),
            with_pins(init_nz_mcpu, nz_mcpu),
            with_pins(init_nz_mem // s_nzmem, nz_mem // s_nzmem),
        )
        if worst >= 2**24:
            return _reject("pinned-pod worst-case usage exceeds f32 exactness")

    # extended scalar resources: per-kind GCD scaling + int32 guards
    s_n = 0
    alloc_scal = iscal0 = req_scal_t = None
    if features.scalars:
        scal_alloc = a(cluster.scalar_alloc, dtype=np.int64)
        req_scalar = a(batch.req_scalar, dtype=np.int64)
        used_scal0 = a(dyn.used_scalar, dtype=np.int64)
        s_n = scal_alloc.shape[0]
        if s_n > 8:
            return _reject(f"{s_n} scalar resource kinds > 8-kind scope")
        scales = []
        for s_i in range(s_n):
            sc = _gcd_scale(scal_alloc[s_i], req_scalar[:, s_i], used_scal0[s_i])
            scales.append(sc)
        scal_s = np.stack([scal_alloc[s_i] // scales[s_i] for s_i in range(s_n)])
        req_s = np.stack(
            [req_scalar[:, s_i] // scales[s_i] for s_i in range(s_n)], axis=1
        )
        used_s0 = np.stack([used_scal0[s_i] // scales[s_i] for s_i in range(s_n)])
        worst_scal = used_s0.max(initial=0)
        if features.pins:
            pin_mask = a(batch.pinned_node) >= 0
            pin_cls = a(batch.class_of_pod)[pin_mask]
            worst_scal = worst_scal + req_s[pin_cls].sum(axis=0).max(initial=0)
        if (
            scal_s.max(initial=0) > _MAX_SCALED
            or req_s.max(initial=0) > _MAX_SCALED
            or worst_scal >= 2**30
        ):
            return _reject("scalar-resource magnitudes exceed int32 exactness")
        alloc_scal = _pad_stack(scal_s, r)
        iscal0 = _pad_stack(used_s0, r)
        req_scal_t = req_s.astype(np.int32).reshape(-1)  # (U*S,) row-major

    # open-gpu-share: per-device memory state (G tiles), tightest-fit /
    # two-pointer allocation mirrored from ops/scan.py _gpu_allocate
    g_n = 0
    gpu_per_dev_s = gpu_cnt_nodes = gpu_tot_s = igpu0 = None
    gpu_mem_u = gpu_cnt_u = None
    if features.gpu:
        gused0_raw = a(dyn.gpu_used, dtype=np.int64)
        # encode pads the device axis to >= 1 even for gpu-free nodes;
        # per_dev = 0 there makes every device unfit, which is correct
        g_n = int(gused0_raw.shape[1])
        if g_n > 8:
            return _reject(f"{g_n} GPU devices per node > 8-device scope")
        gper = a(cluster.gpu_per_dev, dtype=np.int64)
        gcnt = a(cluster.gpu_count, dtype=np.int64)
        gtot = a(cluster.gpu_total, dtype=np.int64)
        bmem = a(batch.gpu_mem, dtype=np.int64)
        s_gpu = _gcd_scale(gper, bmem, gused0_raw)
        gper_s = gper // s_gpu
        gtot_f = gtot // s_gpu  # exact for >= vs scaled bmem (bmem % s == 0)
        bmem_s = bmem // s_gpu
        gused0_s = gused0_raw // s_gpu
        if (
            gper_s.max(initial=0) > _MAX_SCALED
            or gtot_f.max(initial=0) > _MAX_SCALED
            or bmem_s.max(initial=0) > _MAX_SCALED
        ):
            return _reject("gpu-memory magnitudes exceed int32 exactness")
        gpu_per_dev_s = _pad_nodes(gper_s, r)
        gpu_cnt_nodes = _pad_nodes(gcnt, r)
        gpu_tot_s = _pad_nodes(gtot_f, r)
        igpu0 = _pad_stack(np.ascontiguousarray(gused0_s.T), r)
        gpu_mem_u = bmem_s.astype(np.int32)
        gpu_cnt_u = a(batch.gpu_cnt, dtype=np.int64).astype(np.int32)

    # hostPorts: occupancy bitplanes over the port vocab
    pw = 0
    ports0 = want_w = confl_w = None
    if features.ports:
        want_p = a(batch.want_ports).astype(bool)
        confl_p = a(batch.conflict_ports).astype(bool)
        pt = want_p.shape[1]
        if pt > 8 * 32:
            return _reject(f"{pt} distinct host ports > 256-port scope")
        pw = max(-(-pt // 32), 1)
        ports0 = _pad_stack(_pack_bitplanes(a(dyn.ports_used).astype(bool).T), r)

        def pack_words(tab):  # (U, Pt) bool -> (U*Pw,) i32 words
            # same bit layout as the node-space planes (_pack_bitplanes:
            # port p at bit p&31 of word p>>5), transposed to per-class
            words = _pack_bitplanes(tab.T).T  # (U, Pw)
            if words.shape[1] < pw:  # pad classes with no ports
                words = np.pad(words, ((0, 0), (0, pw - words.shape[1])))
            return np.ascontiguousarray(words).reshape(-1)

        want_w = pack_words(want_p)
        confl_w = pack_words(confl_p)

    store = None
    if features.storage:
        store = _build_storage(cluster, batch, dyn, r)
        if store is None:
            return None

    terms = None
    hk_map = None
    if features.ipa or features.hard_spread or features.soft_spread:
        p_total = int(a(batch.class_of_pod).shape[0])
        built = _build_terms(batch, features, r, p_total, n)
        if built is None:
            return None
        terms, hk_map = built

    pre = None
    if preempt is not None:
        pre = _build_preempt(preempt, r, int(a(batch.class_of_pod).shape[0]),
                             s_mem, s_eph, s_nzmem)
        if pre is None:
            return None

    class_scalars = np.zeros((u, 8), dtype=np.int32)
    class_scalars[:, 0] = req_mcpu
    class_scalars[:, 1] = req_mem // s_mem
    class_scalars[:, 2] = req_eph // s_eph
    class_scalars[:, 3] = nz_mcpu
    class_scalars[:, 4] = nz_mem // s_nzmem
    class_scalars[:, 5] = a(batch.has_request).astype(np.int32)

    # class tables deduplicated to distinct rows; clsmap resolves class
    # u -> row per table (big-U batches often share a handful of
    # distinct node patterns across hundreds of classes)
    feas_d, feas_i = _dedup_rows(a(batch.static_feasible).astype(np.int32))
    simon_d, simon_i = _dedup_rows(simon_raw)
    base_d, base_i = _dedup_rows(base_score)
    na_d, na_i = _dedup_rows(nodeaff_raw)
    tt_d, tt_i = _dedup_rows(taint_intol)
    clsmap = np.zeros((8, max(u, 1)), dtype=np.int32)
    clsmap[0, :u] = feas_i
    clsmap[1, :u] = simon_i
    clsmap[2, :u] = base_i
    clsmap[3, :u] = na_i
    clsmap[4, :u] = tt_i
    if hk_map is not None:
        clsmap[5, :u] = hk_map
    clsmap = clsmap.reshape(-1)  # 1-D for SMEM (see TermsPlan note)

    plan = PallasPlan(
        n=n,
        r=r,
        u=u,
        alloc_mcpu=_pad_nodes(alloc_mcpu, r),
        alloc_mem_s=_pad_nodes(alloc_mem // s_mem, r),
        alloc_eph_s=_pad_nodes(alloc_eph // s_eph, r),
        alloc_pods=_pad_nodes(alloc_pods, r),
        alloc_nzmem_s=_pad_nodes(alloc_mem // s_nzmem, r),
        static_feasible=_pad_class_table(feas_d, r),
        simon_raw=_pad_class_table(simon_d, r),
        nodeaff_raw=_pad_class_table(na_d, r),
        taint_intol=_pad_class_table(tt_d, r),
        base_score=_pad_class_table(base_d, r),
        clsmap=clsmap,
        class_scalars=class_scalars,
        init_used_mcpu=_pad_nodes(init_used_mcpu, r),
        init_used_mem_s=_pad_nodes(init_used_mem // s_mem, r),
        init_used_eph_s=_pad_nodes(init_used_eph // s_eph, r),
        init_nz_mcpu=_pad_nodes(init_nz_mcpu, r),
        init_nz_mem_s=_pad_nodes(init_nz_mem // s_nzmem, r),
        init_pod_cnt=_pad_nodes(init_pod_cnt, r),
        s_mem=s_mem,
        s_eph=s_eph,
        s_nzmem=s_nzmem,
        w=(int(w.least), int(w.balanced), int(w.simon) + int(w.gpushare),
           int(w.nodeaff), int(w.tainttol), int(w.spread), int(w.ipa),
           int(w.openlocal)),
        has_nodeaff=bool(nodeaff_raw.any()),
        has_taint=bool(taint_intol.any()),
        has_pins=bool(features.pins),
        terms=terms,
        s_n=s_n,
        alloc_scal=alloc_scal,
        iscal0=iscal0,
        req_scal=req_scal_t,
        pw=pw,
        ports0=ports0,
        want_w=want_w,
        confl_w=confl_w,
        g_n=g_n,
        gpu_per_dev=gpu_per_dev_s,
        gpu_cnt_n=gpu_cnt_nodes,
        gpu_tot=gpu_tot_s,
        igpu0=igpu0,
        gpu_mem_u=gpu_mem_u,
        gpu_cnt_u=gpu_cnt_u,
        store=store,
        pre=pre,
    )

    # VMEM budget (~16MB/core): count the PERSISTENT (R, C) tiles
    # directly from the plan arrays. State-init INPUTS live in ANY
    # (HBM) and are DMAed into scratch, so scratch counts once.
    base_tiles = (
        5  # alloc vectors
        + 6 * 2  # state inputs + output copies
        + 1  # valid
        + plan.static_feasible.shape[0]
        + plan.simon_raw.shape[0]
        + plan.base_score.shape[0]
        + (plan.nodeaff_raw.shape[0] if plan.has_nodeaff else 0)
        + (plan.taint_intol.shape[0] if plan.has_taint else 0)
        + (3 + plan.g_n if plan.g_n else 0)  # gpu statics + used scratch
        + 2 * s_n  # scalar alloc + used scratch
        + pw  # port occupancy planes
        + (
            # caps + storow/has_store + used scratch per slot
            2 * (store.cfg.v + store.cfg.ds + store.cfg.dh) + 2
            if store is not None
            else 0
        )
        + (len(_PRE_FIELDS) * pre.k if pre is not None else 0)  # slot scratch
    )
    tiles = base_tiles
    if terms is not None:
        tc_ = terms.cfg
        tiles += (
            terms.topo_dist.shape[0]
            + terms.g_topo3.shape[0]
            + (terms.cand_dist.shape[0] if tc_.has_hard else 0)
            + (terms.sq_dist.shape[0] if tc_.has_soft else 0)
            + (terms.hk_dist.shape[0] if tc_.has_soft else 0)
            # scratch: tgt + pref + panti + 2 bitplane sets + group + soft
            + tc_.tc + 2 * tc_.tp + 2 * tc_.bp + tc_.a
            + (tc_.csn if tc_.has_soft else 0)
        )
    budget = 13 * 2**20
    rbytes = r * LANES * 4
    # the (Wr, 128) f32 log-weight tables are node-count sized VMEM
    w_bytes = 4 * terms.w_hi.size * 4 if terms is not None else 0
    if tiles * rbytes + w_bytes > budget or (
        STREAM_FORCE and terms is not None
    ):
        # resident term state does not fit: rewrite to the streamed
        # layout (state in HBM, per-pod class-local row gather) before
        # giving up on the fused kernel
        if terms is None or STREAM_FORCE is False:
            return _reject("cluster state exceeds VMEM budget")
        sp = _stream_pack(terms, u, hk_map)
        if sp is None:
            return None  # _stream_pack recorded the reject reason
        stream_bytes = (base_tiles + sp.cfg.kmax) * rbytes + w_bytes + 4 * (
            sp.g_topo3.size + sp.g_match_au.size
            + sp.group0.size + sp.gtot0.size
        )
        if stream_bytes > budget:
            return _reject(
                "cluster state exceeds VMEM budget even with streamed terms"
            )
        smem_entries = sum(
            getattr(sp, nm).size
            for nm, space in _STREAM_TERM_FIELDS
            if space == "smem"
        )
        if smem_entries > _MAX_SMEM_ENTRIES:
            return _reject("terms: streamed SMEM slot tables over budget")
        plan = plan._replace(terms=sp)
    global _LAST_REJECT
    _LAST_REJECT = None
    return plan


def _build_preempt(preempt, r: int, p_total: int, s_mem: int, s_eph: int,
                   s_nzmem: int) -> Optional[PreemptPlan]:
    """The dry run's int32 inputs (PreemptPlan), or None (recorded) when
    they leave the kernel's scope: more than _PRE_MAX_K slots, or
    priorities, sequences or requests past int32 exactness. Priority
    sums run over up to K victims."""
    table, seq_next, prio, ok, hard = preempt
    k = int(table["valid"].shape[0])
    if k > _PRE_MAX_K:
        return _reject(f"preemption: {k} pod slots per node > {_PRE_MAX_K}")
    valid = np.asarray(table["valid"], bool)
    prio_all = np.concatenate([np.asarray(prio, np.int64).ravel(),
                               np.asarray(table["prio"], np.int64)[valid]])
    scaled = {
        "mem": np.asarray(table["mem"], np.int64) // s_mem,
        "eph": np.asarray(table["eph"], np.int64) // s_eph,
        "nz_mem": np.asarray(table["nz_mem"], np.int64) // s_nzmem,
    }
    if (
        np.abs(prio_all).max(initial=0) * (k + 1) >= 2**31
        or seq_next + p_total >= 2**31
        or max(int(np.asarray(scaled.get(f, table[f])).max(initial=0))
               for f in ("mcpu", "mem", "eph", "nz_mcpu", "nz_mem")) > _MAX_SCALED
    ):
        return _reject("preemption: priorities or requests exceed int32 exactness")
    rows = [
        np.asarray(scaled.get(f, table[f])).astype(np.int64) for f in _PRE_FIELDS
    ]
    table0 = np.concatenate([_pad_stack(x, r) for x in rows])
    pr_rows = _pr_rows(p_total)
    pod = np.zeros((3, pr_rows * LANES), np.int32)
    for i, v in enumerate((prio, ok, hard)):
        pod[i, :p_total] = np.asarray(v).astype(np.int64)
    return PreemptPlan(
        k=k, table0=table0, pod=pod.reshape(3, pr_rows, LANES),
        meta=np.array([seq_next], np.int32),
    )


# ordered (TermsPlan field, memory space) spec of the term-block kernel
# inputs — the single source of truth shared by the arg packer
# (_device_args), the BlockSpec assignment, and the kernel's unpacking
_TERM_FIELDS = (
    ("topo_dist", "vmem"), ("g_topo3", "vmem"), ("cand_dist", "vmem"),
    ("sq_dist", "vmem"), ("hk_dist", "vmem"), ("g_match_au", "vmem"),
    ("tgt0_c", "any"), ("pref0_p", "any"), ("panti0_p", "any"),
    ("antib0", "any"), ("tposb0", "any"), ("group0", "any"),
    ("gtot0", "any"), ("soft0_nh", "any"),
    ("e_cnt", "smem"), ("e_pref", "smem"), ("e_cpd", "smem"),
    ("e_antip", "smem"), ("e_antib", "smem"),
    ("e_tposp", "smem"), ("e_tposb", "smem"),
    ("gid_u", "smem"), ("self_ok_u", "smem"), ("slot_grows", "smem"),
    ("h_topo", "smem"), ("h_cnt", "smem"), ("h_cand", "smem"),
    ("h_skew", "smem"), ("h_selfm", "smem"),
    ("s_topo_i", "smem"), ("s_ishost", "smem"), ("s_cnt", "smem"),
    ("s_nh", "smem"), ("s_skewm1", "smem"),
    ("c_topo", "smem"), ("c_cnt", "smem"), ("c_pref", "smem"),
    ("c_m", "smem"), ("c_prefc", "smem"), ("c_pantic", "smem"),
    ("c_antip", "smem"), ("c_antib", "smem"),
    ("c_tposp", "smem"), ("c_tposb", "smem"),
    ("sc_nh", "smem"), ("sc_topo", "smem"), ("sc_q", "smem"),
    ("sc_m", "smem"),
    ("w_hi", "vmem"), ("w_lo", "vmem"), ("w_h1", "vmem"), ("w_h2", "vmem"),
)


class StoreCfg(NamedTuple):
    """Static shape configuration of the open-local storage block
    (part of the compiled-kernel cache key)."""

    v: int  # VG slots per node
    ds: int  # SSD device slots
    dh: int  # HDD device slots
    lv: int  # LVM volume slots per class
    sv: int  # SSD volume slots per class
    hv: int  # HDD volume slots per class
    sd: int  # distinct node storage-config rows
    plvm: int  # v ** lv assignment patterns
    pdev: int  # ds**sv * dh**hv assignment patterns


class StorePlan(NamedTuple):
    """Open-local storage arrays for the fused kernel.

    The VG Binpack choice and the device first-fit are exact integer
    comparisons once every byte quantity is divided by the collective
    GCD (_gcd_scale), so the FILTER and the hypothetical ALLOCATION run
    in int32 bit-identically to the XLA path (ops/scan.py
    _local_storage_eval, open-local algo.go:487,574). The SCORES are
    f64 with truncation in the reference (take/cap means x 10) — the
    r4 measured reason the plugin stayed off the kernel. Instead of
    emulating f64 in-kernel, the score of every reachable outcome is
    precomputed ON THE HOST in real f64: an outcome is fully described
    by (pod class, the node's distinct storage config row, which
    VG/device slot each volume landed on), so the kernel computes the
    assignment PATTERN (a base-V / base-D digit string) during the
    integer binpack and looks the score up from an SMEM table —
    bit-exact against the XLA scan because IEEE division of the
    GCD-scaled integers rounds the same real quotient.
    """

    cfg: StoreCfg
    # VMEM node tiles (caps are GCD-scaled, invalid slots folded to 0)
    vg_cap_s: np.ndarray  # (V, R, C)
    ssd_cap_s: np.ndarray  # (Ds, R, C)
    hdd_cap_s: np.ndarray  # (Dh, R, C)
    has_store: np.ndarray  # (R, C) 0/1
    storow: np.ndarray  # (R, C) distinct storage-config row per node
    # init state (ANY -> scratch)
    ivg0: np.ndarray  # (V, R, C) scaled init requested
    issd0: np.ndarray  # (Ds, R, C) 0/1 allocated
    ihdd0: np.ndarray  # (Dh, R, C) 0/1
    # SMEM class tables (scaled volume sizes; 0 = inactive slot)
    lvm_mi: np.ndarray  # (U*Lv,)
    ssd_mi: np.ndarray  # (U*Sv,)
    hdd_mi: np.ndarray  # (U*Hv,)
    wants_u: np.ndarray  # (U,)
    # SMEM score tables: host-f64 ScoreLVM / ScoreDevice per
    # (class, storage row, assignment pattern)
    lvm_sc: np.ndarray  # (U*Sd*Plvm,)
    dev_sc: np.ndarray  # (U*Sd*Pdev,)
    # the collective GCD dividing every byte quantity — decode uses it
    # to return the exported final VG usage in true bytes
    scale: int = 1


# ordered (StorePlan field, memory space) spec — shared by the arg
# packer, BlockSpec assignment, and kernel unpacking (same contract as
# _TERM_FIELDS)
_STORE_FIELDS = (
    ("vg_cap_s", "vmem"), ("ssd_cap_s", "vmem"), ("hdd_cap_s", "vmem"),
    ("has_store", "vmem"), ("storow", "vmem"),
    ("ivg0", "any"), ("issd0", "any"), ("ihdd0", "any"),
    ("lvm_mi", "smem"), ("ssd_mi", "smem"), ("hdd_mi", "smem"),
    ("wants_u", "smem"), ("lvm_sc", "smem"), ("dev_sc", "smem"),
)

_MAX_STORE = dict(v=4, ds=4, dh=4, lv=4, sv=2, hv=2, sd=16, pat=256)


def _build_storage(cluster, batch, dyn, r: int) -> Optional[StorePlan]:
    """Open-local storage block for the fused kernel, or None (with the
    reject reason recorded) when out of scope."""
    a = np.asarray
    vg_cap = a(cluster.vg_cap, dtype=np.int64) * a(cluster.vg_valid, dtype=np.int64)
    ssd_cap = a(cluster.ssd_cap, dtype=np.int64) * a(cluster.ssd_valid, dtype=np.int64)
    hdd_cap = a(cluster.hdd_cap, dtype=np.int64) * a(cluster.hdd_valid, dtype=np.int64)
    vg_used0 = a(dyn.vg_used, dtype=np.int64)
    ssd_used0 = a(dyn.ssd_used).astype(np.int64)
    hdd_used0 = a(dyn.hdd_used).astype(np.int64)
    lvm = a(batch.lvm_sizes, dtype=np.int64)
    ssd = a(batch.ssd_sizes, dtype=np.int64)
    hdd = a(batch.hdd_sizes, dtype=np.int64)
    wants = a(batch.wants_storage).astype(np.int32)

    v = vg_cap.shape[1]
    ds_n = ssd_cap.shape[1]
    dh_n = hdd_cap.shape[1]
    lv = lvm.shape[1]
    sv = ssd.shape[1]
    hv = hdd.shape[1]
    if (v > _MAX_STORE["v"] or ds_n > _MAX_STORE["ds"]
            or dh_n > _MAX_STORE["dh"] or lv > _MAX_STORE["lv"]
            or sv > _MAX_STORE["sv"] or hv > _MAX_STORE["hv"]):
        return _reject("storage: VG/device/volume slot count over kernel scope")
    plvm = v ** lv
    pdev = (ds_n ** sv) * (dh_n ** hv)
    if plvm > _MAX_STORE["pat"] or pdev > _MAX_STORE["pat"]:
        return _reject("storage: assignment pattern space over kernel scope")

    s = _gcd_scale(vg_cap, ssd_cap, hdd_cap, vg_used0, lvm, ssd, hdd)
    vg_s = vg_cap // s
    ssd_s = ssd_cap // s
    hdd_s = hdd_cap // s
    vgu_s = vg_used0 // s
    lvm_s = lvm // s
    ssd_vs = ssd // s
    hdd_vs = hdd // s
    if max(vg_s.max(initial=0), ssd_s.max(initial=0),
           hdd_s.max(initial=0), vgu_s.max(initial=0),
           # volume sizes must fit int32 too: a size sharing no large
           # GCD with the capacities (scale ~1) would otherwise WRAP in
           # the int32 cast and silently diverge from the XLA scan
           lvm_s.max(initial=0), ssd_vs.max(initial=0),
           hdd_vs.max(initial=0)) > _MAX_SCALED:
        return _reject("storage: scaled capacities exceed int32 exactness")

    # distinct storage-config rows: caps alone determine every score
    # outcome (the dynamic part — takes — is the pattern)
    rows = np.hstack([vg_s, ssd_s, hdd_s])
    dist, storow = _dedup_rows(rows.astype(np.int32))
    sd = max(dist.shape[0], 1)
    if sd > _MAX_STORE["sd"]:
        return _reject("storage: distinct node storage configs over kernel scope")
    if sd * (plvm + pdev) > 256:
        # the in-kernel score lookup unrolls sd*(plvm+pdev) masked
        # selects per pod step; keep the instruction budget bounded
        return _reject("storage: score lookup unroll over kernel budget")

    u_n = lvm.shape[0]
    smem_entries = u_n * (lv + sv + hv + 1) + u_n * sd * (plvm + pdev)
    if smem_entries > _MAX_SMEM_ENTRIES // 2:
        return _reject("storage: score tables over SMEM budget")

    # host-f64 score tables, replicating _local_storage_eval's float
    # op order exactly (scaled values divide to the same real quotient
    # as the raw byte values, so IEEE rounding matches)
    lvm_sc = np.zeros((u_n, sd, plvm), dtype=np.int32)
    dev_sc = np.zeros((u_n, sd, pdev), dtype=np.int32)
    for u_i in range(u_n):
        if not wants[u_i]:
            continue
        for s_i in range(dist.shape[0]):
            caps = dist[s_i]
            vcaps = caps[:v].astype(np.float64)
            scaps = caps[v : v + ds_n].astype(np.float64)
            hcaps = caps[v + ds_n :].astype(np.float64)
            for p in range(plvm):
                takes = [0] * v
                digits = p
                for i in range(lv):
                    j = digits % v if v else 0
                    digits //= max(v, 1)
                    if lvm_s[u_i, i] > 0:
                        takes[j] += int(lvm_s[u_i, i])
                frac = np.float64(0.0)
                cnt = 0
                for j in range(v):
                    if takes[j] > 0:
                        frac += np.float64(takes[j]) / max(vcaps[j], 1.0)
                        cnt += 1
                if cnt > 0:
                    lvm_sc[u_i, s_i, p] = int(frac / max(cnt, 1) * 10.0)
            for q in range(pdev):
                sfrac = np.float64(0.0)
                hfrac = np.float64(0.0)
                cnt = 0
                digits = q
                for i in range(sv):
                    d = digits % ds_n if ds_n else 0
                    digits //= max(ds_n, 1)
                    if ssd_vs[u_i, i] > 0:
                        sfrac += np.float64(ssd_vs[u_i, i]) / max(scaps[d], 1.0)
                        cnt += 1
                for i in range(hv):
                    d = digits % dh_n if dh_n else 0
                    digits //= max(dh_n, 1)
                    if hdd_vs[u_i, i] > 0:
                        hfrac += np.float64(hdd_vs[u_i, i]) / max(hcaps[d], 1.0)
                        cnt += 1
                if cnt > 0:
                    dev_sc[u_i, s_i, q] = int((sfrac + hfrac) / max(cnt, 1) * 10.0)

    cfg = StoreCfg(v=v, ds=ds_n, dh=dh_n, lv=lv, sv=sv, hv=hv, sd=sd,
                   plvm=plvm, pdev=pdev)
    return StorePlan(
        cfg=cfg,
        vg_cap_s=_pad_stack(np.ascontiguousarray(vg_s.T), r),
        ssd_cap_s=_pad_stack(np.ascontiguousarray(ssd_s.T), r),
        hdd_cap_s=_pad_stack(np.ascontiguousarray(hdd_s.T), r),
        has_store=_pad_nodes(
            a(cluster.has_storage).astype(np.int32), r
        ),
        storow=_pad_nodes(storow, r),
        ivg0=_pad_stack(np.ascontiguousarray(vgu_s.T), r),
        issd0=_pad_stack(np.ascontiguousarray(ssd_used0.T), r),
        ihdd0=_pad_stack(np.ascontiguousarray(hdd_used0.T), r),
        lvm_mi=lvm_s.astype(np.int32).reshape(-1),
        ssd_mi=ssd_vs.astype(np.int32).reshape(-1),
        hdd_mi=hdd_vs.astype(np.int32).reshape(-1),
        wants_u=wants,
        lvm_sc=lvm_sc.reshape(-1),
        dev_sc=dev_sc.reshape(-1),
        scale=int(s),
    )


class StreamTermsPlan(NamedTuple):
    """Streamed-terms variant of TermsPlan (cfg.stream=True).

    Past the VMEM budget the resident design cannot hold the term
    state on-chip, but each pod only ever touches the rows its CLASS's
    slot tables reference — at most `kmax` distinct (R, C) node
    vectors. So every T-proportional array (count/pref/bitplane/soft
    state plus the deduplicated topo/cand/sq/haskeys statics) is
    concatenated into ONE (S, R, C) HBM buffer, the slot tables are
    rewritten host-side from array rows to per-class GATHER POSITIONS,
    and the kernel's pod step DMAs the class's row set into a (Kmax,
    R, C) VMEM scratch, runs the IDENTICAL eval/commit arithmetic on
    positions, and DMAs the <= wmax dirty rows back. Per-pod HBM
    traffic is kmax*R*512B (class-local), independent of the total
    term count T — the ~12.3k-node VMEM cliff (docs/PERFORMANCE.md)
    becomes a bandwidth slope instead.

    Only the small required-affinity group machinery (A rows) stays
    resident, because its eval reads every group row per pod.

    pref and panti share one index in the resident tables (same row of
    two arrays); in the unified buffer they are different global rows,
    so this plan carries separate e_panti/c_panti position tables (the
    resident kernel aliases them to e_pref/c_pref)."""

    cfg: TermsCfg
    state0: np.ndarray  # (S, R, C) i32 unified init state + statics (ANY)
    g_topo3: np.ndarray  # (A, R, C) resident group-row topo values
    g_match_au: np.ndarray  # (A, Ur_p, 128)
    group0: np.ndarray  # (A, R, C) DMAed to scratch
    gtot0: np.ndarray  # (A, 8, 128)
    # SMEM slot tables — same semantics as TermsPlan but values are
    # gather positions into the (Kmax, R, C) scratch
    e_cnt: np.ndarray
    e_pref: np.ndarray
    e_panti: np.ndarray
    e_cpd: np.ndarray
    e_antip: np.ndarray
    e_antib: np.ndarray
    e_tposp: np.ndarray
    e_tposb: np.ndarray
    gid_u: np.ndarray
    self_ok_u: np.ndarray
    slot_grows: np.ndarray
    h_topo: np.ndarray
    h_cnt: np.ndarray
    h_cand: np.ndarray
    h_skew: np.ndarray
    h_selfm: np.ndarray
    s_topo_i: np.ndarray
    s_ishost: np.ndarray
    s_cnt: np.ndarray
    s_nh: np.ndarray
    s_skewm1: np.ndarray
    c_topo: np.ndarray
    c_cnt: np.ndarray
    c_pref: np.ndarray
    c_panti: np.ndarray
    c_m: np.ndarray
    c_prefc: np.ndarray
    c_pantic: np.ndarray
    c_antip: np.ndarray
    c_antib: np.ndarray
    c_tposp: np.ndarray
    c_tposb: np.ndarray
    sc_nh: np.ndarray
    sc_topo: np.ndarray
    sc_q: np.ndarray
    sc_m: np.ndarray
    w_hi: np.ndarray
    w_lo: np.ndarray
    w_h1: np.ndarray
    w_h2: np.ndarray
    # streaming tables: per-class gather row ids (-1 = unused slot),
    # write-back (scratch position, global row) pairs (-1 = inactive),
    # per-class haskeys gather position
    gather: np.ndarray  # (U*Kmax,)
    wb_pos: np.ndarray  # (U*Wmax,)
    wb_gid: np.ndarray  # (U*Wmax,)
    hk_pos: np.ndarray  # (U,)


_STREAM_TERM_FIELDS = (
    ("state0", "any"),
    ("g_topo3", "vmem"), ("g_match_au", "vmem"),
    ("group0", "any"), ("gtot0", "any"),
    ("e_cnt", "smem"), ("e_pref", "smem"), ("e_panti", "smem"),
    ("e_cpd", "smem"), ("e_antip", "smem"), ("e_antib", "smem"),
    ("e_tposp", "smem"), ("e_tposb", "smem"),
    ("gid_u", "smem"), ("self_ok_u", "smem"), ("slot_grows", "smem"),
    ("h_topo", "smem"), ("h_cnt", "smem"), ("h_cand", "smem"),
    ("h_skew", "smem"), ("h_selfm", "smem"),
    ("s_topo_i", "smem"), ("s_ishost", "smem"), ("s_cnt", "smem"),
    ("s_nh", "smem"), ("s_skewm1", "smem"),
    ("c_topo", "smem"), ("c_cnt", "smem"), ("c_pref", "smem"),
    ("c_panti", "smem"), ("c_m", "smem"), ("c_prefc", "smem"),
    ("c_pantic", "smem"), ("c_antip", "smem"), ("c_antib", "smem"),
    ("c_tposp", "smem"), ("c_tposb", "smem"),
    ("sc_nh", "smem"), ("sc_topo", "smem"), ("sc_q", "smem"),
    ("sc_m", "smem"),
    ("w_hi", "vmem"), ("w_lo", "vmem"), ("w_h1", "vmem"), ("w_h2", "vmem"),
    ("gather", "smem"), ("wb_pos", "smem"), ("wb_gid", "smem"),
    ("hk_pos", "smem"),
)


def _stream_pack(terms: TermsPlan, u_n: int,
                 hk_map: Optional[np.ndarray]) -> Optional[StreamTermsPlan]:
    """Rewrite a resident TermsPlan into the streamed layout (see
    StreamTermsPlan docstring), or None when a class's row set exceeds
    the gather/write-back slot caps."""
    cfg = terms.cfg
    parts = [terms.tgt0_c, terms.pref0_p, terms.panti0_p, terms.antib0,
             terms.tposb0, terms.soft0_nh, terms.topo_dist,
             terms.cand_dist, terms.sq_dist, terms.hk_dist]
    offs = np.cumsum([0] + [p.shape[0] for p in parts])
    (b_tgt, b_pref, b_panti, b_anti, b_tpos, b_soft, b_topo, b_cand,
     b_sq, b_hk) = (int(o) for o in offs[:10])
    state0 = np.ascontiguousarray(np.concatenate(parts, axis=0))

    def t2(name, m):
        return np.asarray(getattr(terms, name)).reshape(u_n, m).copy()

    e_cnt = t2("e_cnt", cfg.rmax)
    e_pref = t2("e_pref", cfg.rmax)
    e_antip = t2("e_antip", cfg.rmax)
    e_antib = t2("e_antib", cfg.rmax)
    e_tposp = t2("e_tposp", cfg.rmax)
    e_tposb = t2("e_tposb", cfg.rmax)
    h_topo = t2("h_topo", cfg.hmax)
    h_cnt = t2("h_cnt", cfg.hmax)
    h_cand = t2("h_cand", cfg.hmax)
    s_topo_i = t2("s_topo_i", cfg.smax)
    s_cnt = t2("s_cnt", cfg.smax)
    s_nh = t2("s_nh", cfg.smax)
    c_topo = t2("c_topo", cfg.cmax)
    c_cnt = t2("c_cnt", cfg.cmax)
    c_pref = t2("c_pref", cfg.cmax)
    c_antip = t2("c_antip", cfg.cmax)
    c_antib = t2("c_antib", cfg.cmax)
    c_tposp = t2("c_tposp", cfg.cmax)
    c_tposb = t2("c_tposb", cfg.cmax)
    sc_nh = t2("sc_nh", cfg.scmax)
    sc_topo = t2("sc_topo", cfg.scmax)
    sc_q = t2("sc_q", cfg.scmax)
    n_panti = np.full((u_n, cfg.rmax), -1, dtype=np.int32)
    nc_panti = np.full((u_n, cfg.cmax), -1, dtype=np.int32)
    hk_pos = np.zeros(u_n, dtype=np.int32)

    glists: list = []
    wlists: list = []
    for u_i in range(u_n):
        pos: dict = {}

        def g(gid: int) -> int:
            p = pos.get(gid)
            if p is None:
                p = len(pos)
                pos[gid] = p
            return p

        for k in range(cfg.rmax):
            if e_cnt[u_i, k] >= 0:
                e_cnt[u_i, k] = g(b_tgt + e_cnt[u_i, k])
            if e_pref[u_i, k] >= 0:
                row = int(e_pref[u_i, k])
                e_pref[u_i, k] = g(b_pref + row)
                n_panti[u_i, k] = g(b_panti + row)
            e_antip[u_i, k] = (
                g(b_anti + e_antip[u_i, k]) if e_antib[u_i, k] != 0 else 0
            )
            e_tposp[u_i, k] = (
                g(b_tpos + e_tposp[u_i, k]) if e_tposb[u_i, k] != 0 else 0
            )
        for k in range(cfg.hmax):
            if h_topo[u_i, k] >= 0:
                h_topo[u_i, k] = g(b_topo + h_topo[u_i, k])
                h_cnt[u_i, k] = g(b_tgt + h_cnt[u_i, k])
                h_cand[u_i, k] = g(b_cand + h_cand[u_i, k])
        for k in range(cfg.smax):
            if s_topo_i[u_i, k] >= 0:
                s_topo_i[u_i, k] = g(b_topo + s_topo_i[u_i, k])
                if s_cnt[u_i, k] >= 0:
                    s_cnt[u_i, k] = g(b_tgt + s_cnt[u_i, k])
                if s_nh[u_i, k] >= 0:
                    s_nh[u_i, k] = g(b_soft + s_nh[u_i, k])
        if cfg.has_soft and hk_map is not None:
            hk_pos[u_i] = g(b_hk + int(hk_map[u_i]))
        # write-backs: every position a commit slot mutates
        wb: "OrderedDict" = OrderedDict()
        for j in range(cfg.cmax):
            if c_topo[u_i, j] >= 0:
                c_topo[u_i, j] = g(b_topo + c_topo[u_i, j])
            if c_cnt[u_i, j] >= 0:
                gid = b_tgt + int(c_cnt[u_i, j])
                p = g(gid)
                c_cnt[u_i, j] = p
                wb.setdefault(p, gid)
            if c_pref[u_i, j] >= 0:
                row = int(c_pref[u_i, j])
                gp, ga = b_pref + row, b_panti + row
                c_pref[u_i, j] = g(gp)
                nc_panti[u_i, j] = g(ga)
                wb.setdefault(g(gp), gp)
                wb.setdefault(g(ga), ga)
            if c_antib[u_i, j] != 0:
                gid = b_anti + int(c_antip[u_i, j])
                c_antip[u_i, j] = g(gid)
                wb.setdefault(g(gid), gid)
            else:
                c_antip[u_i, j] = 0
            if c_tposb[u_i, j] != 0:
                gid = b_tpos + int(c_tposp[u_i, j])
                c_tposp[u_i, j] = g(gid)
                wb.setdefault(g(gid), gid)
            else:
                c_tposp[u_i, j] = 0
        for j in range(cfg.scmax):
            if sc_nh[u_i, j] >= 0:
                gid = b_soft + int(sc_nh[u_i, j])
                sc_nh[u_i, j] = g(gid)
                wb.setdefault(g(gid), gid)
                sc_topo[u_i, j] = g(b_topo + sc_topo[u_i, j])
                sc_q[u_i, j] = g(b_sq + sc_q[u_i, j])
        glists.append(list(pos.keys()))
        wlists.append(list(wb.items()))

    kmax = max((len(gl) for gl in glists), default=0)
    kmax = max(kmax, 1)
    wmax = max((len(wl) for wl in wlists), default=0)
    wmax = max(wmax, 1)
    if kmax > _MAX_SLOTS["kmax"] or wmax > _MAX_SLOTS["wmax"]:
        return _reject("terms: per-class streamed row set over gather caps")
    gather = np.full((u_n, kmax), -1, dtype=np.int32)
    for u_i, gl in enumerate(glists):
        gather[u_i, : len(gl)] = gl
    wb_pos = np.zeros((u_n, wmax), dtype=np.int32)
    wb_gid = np.full((u_n, wmax), -1, dtype=np.int32)
    for u_i, wl in enumerate(wlists):
        for j, (p, gid) in enumerate(wl):
            wb_pos[u_i, j] = p
            wb_gid[u_i, j] = gid

    ncfg = cfg._replace(stream=True, kmax=kmax, wmax=wmax,
                        srows=int(state0.shape[0]))
    return StreamTermsPlan(
        cfg=ncfg,
        state0=state0,
        g_topo3=terms.g_topo3,
        g_match_au=terms.g_match_au,
        group0=terms.group0,
        gtot0=terms.gtot0,
        e_cnt=e_cnt.reshape(-1), e_pref=e_pref.reshape(-1),
        e_panti=n_panti.reshape(-1),
        e_cpd=terms.e_cpd,
        e_antip=e_antip.reshape(-1), e_antib=terms.e_antib,
        e_tposp=e_tposp.reshape(-1), e_tposb=terms.e_tposb,
        gid_u=terms.gid_u, self_ok_u=terms.self_ok_u,
        slot_grows=terms.slot_grows,
        h_topo=h_topo.reshape(-1), h_cnt=h_cnt.reshape(-1),
        h_cand=h_cand.reshape(-1), h_skew=terms.h_skew,
        h_selfm=terms.h_selfm,
        s_topo_i=s_topo_i.reshape(-1), s_ishost=terms.s_ishost,
        s_cnt=s_cnt.reshape(-1), s_nh=s_nh.reshape(-1),
        s_skewm1=terms.s_skewm1,
        c_topo=c_topo.reshape(-1), c_cnt=c_cnt.reshape(-1),
        c_pref=c_pref.reshape(-1), c_panti=nc_panti.reshape(-1),
        c_m=terms.c_m, c_prefc=terms.c_prefc, c_pantic=terms.c_pantic,
        c_antip=c_antip.reshape(-1), c_antib=terms.c_antib,
        c_tposp=c_tposp.reshape(-1), c_tposb=terms.c_tposb,
        sc_nh=sc_nh.reshape(-1), sc_topo=sc_topo.reshape(-1),
        sc_q=sc_q.reshape(-1), sc_m=terms.sc_m,
        w_hi=terms.w_hi, w_lo=terms.w_lo, w_h1=terms.w_h1,
        w_h2=terms.w_h2,
        gather=gather.reshape(-1),
        wb_pos=wb_pos.reshape(-1),
        wb_gid=wb_gid.reshape(-1),
        hk_pos=hk_pos,
    )


def _make_kernel(p_total: int, u_n: int, w: tuple, has_nodeaff: bool,
                 has_taint: bool, has_pins: bool, s_n: int, g_n: int,
                 pw: int, sc: Optional[StoreCfg], tc: Optional[TermsCfg],
                 pk: int = 0):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    w_least, w_bal, w_simon, w_na, w_tt, w_spread, w_ipa, w_ol = w

    # ---- ref layout: base inputs, term inputs, outputs, term scratch.
    # The na/tt class tables ride along only when their scores are live
    # (a [U, R, C] tile each — meaningful VMEM at U=100).
    BASE_IN = (
        18 + int(has_nodeaff) + int(has_taint)
        + (3 if s_n else 0) + (6 if g_n else 0) + (3 if pw else 0)
        + (len(_STORE_FIELDS) if sc is not None else 0)
        + (3 if pk else 0)
    )
    stream = tc is not None and tc.stream
    term_fields = _STREAM_TERM_FIELDS if stream else _TERM_FIELDS
    TERM_IN = len(term_fields) if tc is not None else 0
    # storage plans export the final VG usage (capacity vg_util reads
    # it); streamed plans append the mutated HBM state buffer as an
    # extra output (ANY space; never fetched to the host)
    N_OUT = 7 + int(sc is not None) + int(stream) + (2 if pk else 0)

    def two_sum(a, b):
        # Knuth 2Sum (branch-free, round-to-nearest f32): s + err == a + b
        s = a + b
        bb = s - a
        err = (a - (s - bb)) + (b - bb)
        return s, err

    def kernel(*refs):
        it = iter(refs[:BASE_IN])
        pod_scal_ref = next(it)  # (8, Pr, 128) i32: class, rc, rm, re,
        #   nzc, nzm, has_req, unused — pod p at [:, p//128, p%128]
        active_ref = next(it)  # (Pr, 128) i32
        valid_ref = next(it)  # (R, C) i32
        clsmap_ref = next(it)  # (8*U,) SMEM: class -> dedup table row,
        #   flattened row-major (table t, class u at [t * u_n + u])
        alloc_c_ref = next(it)
        alloc_m_ref = next(it)
        alloc_e_ref = next(it)
        alloc_p_ref = next(it)
        alloc_nzm_ref = next(it)
        feas_ref = next(it)  # (Fd, R, C) dedup rows
        simon_ref = next(it)
        na_ref = next(it) if has_nodeaff else None
        tt_ref = next(it) if has_taint else None
        base_ref = next(it)
        ic_ref = next(it)  # init-state inputs, copied into the state
        im_ref = next(it)  # outputs at kernel start (output aliasing
        ie_ref = next(it)  # does NOT initialize aliased outputs on TPU
        inzc_ref = next(it)  # — unread inputs are elided)
        inzm_ref = next(it)
        ipc_ref = next(it)
        if s_n:
            scal_alloc_ref = next(it)  # (S, R, C) VMEM
            iscal0_ref = next(it)  # (S, R, C) ANY, DMAed to scratch
            reqscal_ref = next(it)  # (U*S,) SMEM
        if g_n:
            gperdev_ref = next(it)  # (R, C) VMEM per-device memory
            gcntn_ref = next(it)  # (R, C) VMEM device counts
            gtot_ref = next(it)  # (R, C) VMEM capacity gpu-mem
            igpu0_ref = next(it)  # (G, R, C) ANY, DMAed to scratch
            gmem_ref = next(it)  # (U,) SMEM per-GPU request
            gcnt_ref = next(it)  # (U,) SMEM device count
        if pw:
            ports0_ref = next(it)  # (Pw, R, C) ANY, DMAed to scratch
            wantw_ref = next(it)  # (U*Pw,) SMEM
            conflw_ref = next(it)  # (U*Pw,) SMEM
        if sc is not None:
            srf = {nm: next(it) for nm, _ in _STORE_FIELDS}
        if pk:
            pre_tab0_ref = next(it)  # (F*K, R, C) ANY, DMAed to scratch
            pre_pod_ref = next(it)  # (3, Pr, C): priority, allowed, hard
            pre_meta_ref = next(it)  # (1,) SMEM next commit sequence
        if tc is not None:
            tr = dict(zip((nm for nm, _ in term_fields),
                          refs[BASE_IN : BASE_IN + TERM_IN]))
            if not stream:
                topo_ref = tr["topo_dist"]
                cand_ref = tr["cand_dist"]
                sq_ref = tr["sq_dist"]
                haskeys_ref = tr["hk_dist"]
                # pref/panti share one index in the resident layout;
                # the body reads the *_panti tables uniformly
                tr["e_panti"] = tr["e_pref"]
                tr["c_panti"] = tr["c_pref"]
            gtopo_ref = tr["g_topo3"]
            gmatch_ref = tr["g_match_au"]
            gid_ref = tr["gid_u"]
            selfok_ref = tr["self_ok_u"]
            sgrows_ref = tr["slot_grows"]
            whi_ref, wlo_ref = tr["w_hi"], tr["w_lo"]
            wh1_ref, wh2_ref = tr["w_h1"], tr["w_h2"]
        outs = refs[BASE_IN + TERM_IN : BASE_IN + TERM_IN + N_OUT]
        (place_ref, st_c_ref, st_m_ref, st_e_ref,
         st_nzc_ref, st_nzm_ref, st_p_ref) = outs[:7]
        oi = 7
        if sc is not None:
            vg_out_ref = outs[oi]
            oi += 1
        state_out_ref = outs[oi] if stream else None
        if pk:
            pre_node_ref, pre_vic_ref = outs[-2:]
        extra = refs[BASE_IN + TERM_IN + N_OUT :]
        ei = 0
        if s_n:
            uscal_s = extra[ei]
            ei += 1
        if g_n:
            ugpu_s = extra[ei]
            ei += 1
        if pw:
            ports_pl = extra[ei]
            ei += 1
        if sc is not None:
            vgu_s, ssdu_s, hddu_s = extra[ei : ei + 3]
            ei += 3
        if tc is not None:
            if stream:
                group_s, gtot_s, gath_s = extra[ei : ei + 3]
                ei += 3
                state_sem = extra[ei]
                ei += 1
                # every streamed array lives in the one gathered
                # scratch; the body's reads/commits index POSITIONS
                tgt_s = pref_s = panti_s = gath_s
                antib_s = tposb_s = soft_s = gath_s
                topo_ref = cand_ref = sq_ref = gath_s
            else:
                (tgt_s, pref_s, panti_s, antib_s, tposb_s, group_s,
                 gtot_s, soft_s) = extra[ei : ei + 8]
                ei += 8
        if pk:
            tab_s, flag_s = extra[ei : ei + 2]
            ei += 2
        if s_n or g_n or pw or sc is not None or tc is not None or pk:
            dma_sem = extra[ei]

        shape = valid_ref.shape
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        idx_mat = rows * LANES + cols
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        valid = valid_ref[:] != 0
        alloc_c = alloc_c_ref[:]
        alloc_m = alloc_m_ref[:]
        alloc_e = alloc_e_ref[:]
        alloc_p = alloc_p_ref[:]
        alloc_nzm = alloc_nzm_ref[:]
        alloc_c_f = alloc_c.astype(jnp.float32)
        alloc_nzm_f = alloc_nzm.astype(jnp.float32)

        st_c_ref[:] = ic_ref[:]
        st_m_ref[:] = im_ref[:]
        st_e_ref[:] = ie_ref[:]
        st_nzc_ref[:] = inzc_ref[:]
        st_nzm_ref[:] = inzm_ref[:]
        st_p_ref[:] = ipc_ref[:]
        if pk:
            flag_s[0] = jnp.int32(0)
            flag_s[1] = pre_meta_ref[0]
        if s_n or g_n or pw or sc is not None or tc is not None or pk:
            # init states arrive in ANY (HBM) so they do not double the
            # VMEM footprint of their scratch copies; one DMA each
            from jax.experimental.pallas import tpu as pltpu_mod

            copies = []
            if s_n:
                copies.append((iscal0_ref, uscal_s))
            if g_n:
                copies.append((igpu0_ref, ugpu_s))
            if pw:
                copies.append((ports0_ref, ports_pl))
            if pk:
                copies.append((pre_tab0_ref, tab_s))
            if sc is not None:
                copies += [
                    (srf["ivg0"], vgu_s),
                    (srf["issd0"], ssdu_s),
                    (srf["ihdd0"], hddu_s),
                ]
            if tc is not None:
                if stream:
                    # the mutable HBM state starts as a copy of the
                    # device-cached init buffer (one full-array DMA per
                    # CALL, not per pod) so repeated calls on one plan
                    # never re-upload from the host
                    copies += [
                        (tr["state0"], state_out_ref),
                        (tr["group0"], group_s),
                        (tr["gtot0"], gtot_s),
                    ]
                else:
                    copies += [
                        (tr["tgt0_c"], tgt_s),
                        (tr["pref0_p"], pref_s),
                        (tr["panti0_p"], panti_s),
                        (tr["antib0"], antib_s),
                        (tr["tposb0"], tposb_s),
                        (tr["group0"], group_s),
                        (tr["gtot0"], gtot_s),
                        (tr["soft0_nh"], soft_s),
                    ]
            for src_ref, dst_ref in copies:
                cp = pltpu_mod.make_async_copy(src_ref, dst_ref, dma_sem)
                cp.start()
                cp.wait()

        def dry_run(pr, lane, prio, rc, rm, re, has_req, feas_u,
                    used_c, used_m, used_e, used_nzc, used_nzm, pod_cnt):
            """DefaultPreemption's dry run, pick and eviction for the pod
            at row `pr` (ops/preempt.dry_run over int32 tiles; slot sets
            as K-bit masks): writes the evicted state, the compacted
            slots and the pod's placement, preemption node and victim
            bits."""
            F = {f: i for i, f in enumerate(_PRE_FIELDS)}

            def slot(f, k):
                return tab_s[F[f] * pk + k]

            def bit(mask, k):
                return ((mask >> k) & 1) != 0

            def fits(c, m, e, n):
                fit = (c + rc <= alloc_c) & (m + rm <= alloc_m) & (e + re <= alloc_e)
                return (n + 1 <= alloc_p) & (fit | (has_req == 0))

            lower = jnp.zeros(shape, jnp.int32)
            hard = jnp.zeros(shape, jnp.int32)
            for k in range(pk):
                low_k = (slot("valid", k) != 0) & (slot("prio", k) < prio)
                lower = lower | jnp.where(low_k, 1 << k, 0)
                hard = hard | jnp.where(low_k & (slot("hard", k) != 0), 1 << k, 0)

            def colsum(f, mask):
                return sum(jnp.where(bit(mask, k), slot(f, k), 0) for k in range(pk))

            n_lower = sum(jnp.where(bit(lower, k), 1, 0) for k in range(pk))
            c = used_c - colsum("mcpu", lower)
            m = used_m - colsum("mem", lower)
            e = used_e - colsum("eph", lower)
            n = pod_cnt - n_lower
            # nodesWherePreemptionMightHelp + the fit with every lower pod gone
            cand = feas_u & valid & (n_lower > 0) & fits(c, m, e, n)
            escape = (flag_s[0] != 0) | jnp.any(cand & (hard != 0))

            def reprieve(_, carry):
                # the most important remaining pod (priority descending,
                # earlier commit first) goes back if the pod still fits
                rem, vic, c, m, e, n = carry
                bp = jnp.full(shape, NEG, jnp.int32)
                bs = jnp.full(shape, BIG, jnp.int32)
                bk = jnp.full(shape, pk, jnp.int32)
                for k in range(pk):
                    p_k, s_k = slot("prio", k), slot("seq", k)
                    better = bit(rem, k) & ((p_k > bp) | ((p_k == bp) & (s_k < bs)))
                    bp = jnp.where(better, p_k, bp)
                    bs = jnp.where(better, s_k, bs)
                    bk = jnp.where(better, k, bk)
                pick = sum(jnp.where(bk == k, 1 << k, 0) for k in range(pk))
                pc, pm, pe = colsum("mcpu", pick), colsum("mem", pick), colsum("eph", pick)
                keep = (bk < pk) & fits(c + pc, m + pm, e + pe, n + 1)
                return (
                    rem & ~pick,
                    vic | jnp.where(keep, 0, pick),
                    c + jnp.where(keep, pc, 0),
                    m + jnp.where(keep, pm, 0),
                    e + jnp.where(keep, pe, 0),
                    n + jnp.where(keep, 1, 0),
                )

            rounds = jnp.max(jnp.where(cand, n_lower, 0))
            rem0 = jnp.where(cand, lower, 0)
            _, vic, *_ = jax.lax.fori_loop(
                0, rounds, reprieve, (rem0, jnp.zeros(shape, jnp.int32), c, m, e, n)
            )

            # pickOneNodeForPreemption: lowest top victim priority, lowest
            # priority sum, fewest victims, latest earliest start among the
            # top-priority victims, first node
            nv = sum(jnp.where(bit(vic, k), 1, 0) for k in range(pk))
            pool = cand & (nv > 0)
            top = jnp.full(shape, NEG, jnp.int32)
            for k in range(pk):
                top = jnp.where(bit(vic, k), jnp.maximum(top, slot("prio", k)), top)
            psum = colsum("prio", vic)
            early = jnp.full(shape, BIG, jnp.int32)
            for k in range(pk):
                early = jnp.where(
                    bit(vic, k) & (slot("prio", k) == top),
                    jnp.minimum(early, slot("seq", k)), early,
                )
            for x in (top, psum, nv):
                pool = pool & (x == jnp.min(jnp.where(pool, x, BIG)))
            pool = pool & (early == jnp.max(jnp.where(pool, early, NEG)))
            found = jnp.any(pool) & ~escape
            chosen = jnp.min(jnp.where(pool, idx_mat, BIG))

            # the eviction: release the victims, then compact the chosen
            # node's slots so valid ones stay a prefix (= ns.pods order)
            selc = (idx_mat == chosen) & found
            gone = jnp.where(selc, vic, 0)
            st_c_ref[:] = used_c - colsum("mcpu", gone)
            st_m_ref[:] = used_m - colsum("mem", gone)
            st_e_ref[:] = used_e - colsum("eph", gone)
            st_nzc_ref[:] = used_nzc - colsum("nz_mcpu", gone)
            st_nzm_ref[:] = used_nzm - colsum("nz_mem", gone)
            st_p_ref[:] = pod_cnt - sum(jnp.where(bit(gone, k), 1, 0) for k in range(pk))
            kept = jnp.sum(jnp.where(
                selc,
                sum(jnp.where((slot("valid", k) != 0) & ~bit(vic, k), 1 << k, 0)
                    for k in range(pk)),
                0,
            ))

            def compact(j, src):
                # slot j takes the next kept slot at or after `src`
                nxt = jnp.int32(pk)
                for k in reversed(range(pk)):
                    nxt = jnp.where((k >= src) & (((kept >> k) & 1) != 0), k, nxt)
                for f in range(len(_PRE_FIELDS)):
                    v = jnp.where(nxt < pk, tab_s[f * pk + jnp.minimum(nxt, pk - 1)], 0)
                    tab_s[f * pk + j] = jnp.where(selc, v, tab_s[f * pk + j])
                return nxt + 1

            jax.lax.fori_loop(0, pk, compact, jnp.int32(0))

            pre = jnp.where(escape, -2, jnp.where(found, chosen, -1))
            for ref, v in (
                (place_ref, jnp.where(found, chosen, -1)),
                (pre_node_ref, pre),
                (pre_vic_ref, jnp.sum(gone)),
            ):
                row = ref[pl.ds(pr, 1), :]
                ref[pl.ds(pr, 1), :] = jnp.where(lane, v, row)

        def record_commit(sel, pod_cnt, vals):
            """Append a committed pod to its node's slots at slot
            pod_cnt (ops/preempt.record_commit); past the last slot the
            overflow flag turns every later dry run into an escape."""
            flag_s[0] = jnp.maximum(
                flag_s[0], jnp.any(sel & (pod_cnt >= pk)).astype(jnp.int32)
            )
            for k in range(pk):
                put = sel & (pod_cnt == k)
                for f, v in vals.items():
                    i = _PRE_FIELDS.index(f) * pk + k
                    tab_s[i] = jnp.where(put, v, tab_s[i])

        def step(p, prev_u):
            # carry = previous pod's class (streamed-terms gather skip;
            # -1 before the first pod). Dynamic lane-dim loads are
            # unsupported on TPU: read the pod's 128-lane row and
            # extract via a masked reduce
            pr = p // LANES
            pc = p % LANES
            lane = lane_iota == pc

            def pod_scalar(s):
                row = pod_scal_ref[s, pl.ds(pr, 1), :]
                return jnp.sum(jnp.where(lane, row, 0))

            u = pod_scalar(0)
            rc = pod_scalar(1)
            rm = pod_scalar(2)
            re = pod_scalar(3)
            nzc = pod_scalar(4)
            nzm = pod_scalar(5)
            has_req = pod_scalar(6)
            active = jnp.sum(jnp.where(lane, active_ref[pl.ds(pr, 1), :], 0))
            # dedup-table rows for this pod's class (SMEM scalar reads)
            fu = clsmap_ref[u]
            su = clsmap_ref[u_n + u]
            bu = clsmap_ref[2 * u_n + u]

            if stream:
                # gather this class's term-state rows from HBM into the
                # (Kmax, R, C) scratch — ONLY on a class switch. While
                # consecutive pods share a class the scratch stays
                # authoritative (commits land in-scratch) and the dirty
                # rows of the PREVIOUS class are flushed back here, so
                # replica runs pay one gather+flush per class, not per
                # pod. All fetches start first (round-robin over the
                # semaphore array) so they overlap, then one wait pass;
                # positions beyond a class's row set (gid < 0) are
                # skipped and never read by the tables.
                @pl.when(u != prev_u)
                def _switch():
                    _flush_class(jnp.maximum(prev_u, 0), prev_u >= 0)
                    for k in range(tc.kmax):
                        g_k = tr["gather"][u * tc.kmax + k]

                        @pl.when(g_k >= 0)
                        def _(k=k, g_k=g_k):
                            pltpu_mod.make_async_copy(
                                state_out_ref.at[pl.ds(g_k, 1)],
                                gath_s.at[pl.ds(k, 1)],
                                state_sem.at[k % _STREAM_NSEM],
                            ).start()
                    for k in range(tc.kmax):
                        g_k = tr["gather"][u * tc.kmax + k]

                        @pl.when(g_k >= 0)
                        def _(k=k, g_k=g_k):
                            pltpu_mod.make_async_copy(
                                state_out_ref.at[pl.ds(g_k, 1)],
                                gath_s.at[pl.ds(k, 1)],
                                state_sem.at[k % _STREAM_NSEM],
                            ).wait()

            used_c = st_c_ref[:]
            used_m = st_m_ref[:]
            used_e = st_e_ref[:]
            st_nzc = st_nzc_ref[:]
            st_nzm = st_nzm_ref[:]
            pod_cnt = st_p_ref[:]

            fit = (
                (used_c + rc <= alloc_c)
                & (used_m + rm <= alloc_m)
                & (used_e + re <= alloc_e)
            )
            if s_n:
                # extended scalar resources join NodeResourcesFit
                # (fit.go scalar path), inside the zero-request gate
                for s in range(s_n):
                    rq = reqscal_ref[u * s_n + s]
                    fit = fit & (uscal_s[s] + rq <= scal_alloc_ref[s])
            if g_n:
                # open-gpu-share filter + allocation choice, mirroring
                # ops/scan.py _gpu_allocate exactly: tightest fit
                # (strict '<', first device on ties) for one GPU,
                # two-pointer greedy prefix in device order for several
                gm = gmem_ref[u]
                gc = gcnt_ref[u]
                gm1 = jnp.maximum(gm, 1)
                perdev = gperdev_ref[:]
                cntn = gcntn_ref[:]
                gpu_fits_any = jnp.zeros(shape, bool)
                gpu_best_key = jnp.full(shape, BIG, jnp.int32)
                gpu_best_dev = jnp.full(shape, -1, jnp.int32)
                gpu_caps = []
                gpu_prefix = []
                run_prefix = jnp.zeros(shape, jnp.int32)
                for g in range(g_n):
                    dvalid = cntn > g
                    availg = perdev - ugpu_s[g]
                    fitg = dvalid & (availg >= gm)
                    gpu_fits_any = gpu_fits_any | fitg
                    keyg = jnp.where(fitg, availg, BIG)
                    better = keyg < gpu_best_key
                    gpu_best_key = jnp.where(better, keyg, gpu_best_key)
                    gpu_best_dev = jnp.where(better, g, gpu_best_dev)
                    capg = jnp.maximum(
                        jnp.where(dvalid, availg // gm1, 0), 0
                    )
                    gpu_caps.append(capg)
                    gpu_prefix.append(run_prefix)
                    run_prefix = run_prefix + capg
                needs_gpu = gm > 0
                # select over i32 (Mosaic cannot legalize i1-vector
                # select), same pattern as the pin override
                gpu_found = (
                    jnp.where(
                        gc == 1,
                        gpu_fits_any.astype(jnp.int32),
                        (run_prefix >= gc).astype(jnp.int32),
                    )
                    != 0
                )
                gpu_ok = ~needs_gpu | ((gtot_ref[:] >= gm) & gpu_found)
            feas = (
                (feas_ref[fu] != 0)
                & valid
                & (pod_cnt + 1 <= alloc_p)
                & (fit | (has_req == 0))
            )
            if g_n:
                feas = feas & gpu_ok
            if pw:
                # NodePorts: conflict when any occupied port matches the
                # class's conflict mask (HostPortInfo.CheckConflict)
                clash = jnp.zeros(shape, bool)
                for w_i in range(pw):
                    clash = clash | (
                        (ports_pl[w_i] & conflw_ref[u * pw + w_i]) != 0
                    )
                feas = feas & ~clash

            if sc is not None:
                # open-local: VG Binpack + exclusive-device first-fit,
                # mirroring ops/scan.py _local_storage_eval in scaled
                # int32. The assignment PATTERN (base-V/base-D digit
                # string) indexes the host-f64 score tables later.
                wants_s = srf["wants_u"][u]
                lvm_ok = jnp.ones(shape, bool)
                pat_lvm = jnp.zeros(shape, jnp.int32)
                take_vg = [jnp.zeros(shape, jnp.int32) for _ in range(sc.v)]
                vg_free = [
                    srf["vg_cap_s"][j] - vgu_s[j] for j in range(sc.v)
                ]
                for i in range(sc.lv):
                    vsz = srf["lvm_mi"][u * sc.lv + i]
                    act = (vsz > 0).astype(jnp.int32)
                    best_free = jnp.full(shape, BIG, jnp.int32)
                    best_j = jnp.zeros(shape, jnp.int32)
                    for j in range(sc.v):
                        fj = vg_free[j] - take_vg[j]
                        # cap=0 (invalid VG) keeps fj <= 0 < vsz for any
                        # active volume, so validity needs no extra mask
                        keyj = jnp.where(fj >= vsz, fj, BIG)
                        better = keyj < best_free  # strict: ties keep lowest j
                        best_free = jnp.where(better, keyj, best_free)
                        best_j = jnp.where(better, j, best_j)
                    ok_i = best_free < BIG
                    for j in range(sc.v):
                        selj = ok_i & (best_j == j)
                        take_vg[j] = take_vg[j] + jnp.where(selj, vsz, 0)
                    lvm_ok = lvm_ok & (ok_i | (act == 0))
                    pat_lvm = pat_lvm + (
                        jnp.where(ok_i, best_j, 0) * ((sc.v ** i) * act)
                    )

                def fit_dev(d_n, vol_n, cap_nm, used_s, mi_nm, mult0):
                    """First-fit ascending sizes onto the first free
                    device with room (scan.py fit_devices); returns
                    (ok, taken per slot, pattern contribution)."""
                    d_ok = jnp.ones(shape, bool)
                    pat = jnp.zeros(shape, jnp.int32)
                    taken = [jnp.zeros(shape, bool) for _ in range(d_n)]
                    mult = mult0
                    for i in range(vol_n):
                        dsz = srf[mi_nm][u * vol_n + i]
                        act_d = (dsz > 0).astype(jnp.int32)
                        found = jnp.zeros(shape, bool)
                        chosen = jnp.zeros(shape, jnp.int32)
                        for d in range(d_n):
                            cd = srf[cap_nm][d]
                            elig = (
                                (used_s[d] == 0)
                                & ~taken[d]
                                & (cd >= dsz)
                                & (cd > 0)
                            )
                            newly = elig & ~found
                            chosen = jnp.where(newly, d, chosen)
                            found = found | elig
                        for d in range(d_n):
                            seld = found & (chosen == d) & (act_d != 0)
                            taken[d] = taken[d] | seld
                        d_ok = d_ok & (found | (act_d == 0))
                        pat = pat + jnp.where(found, chosen, 0) * (mult * act_d)
                        mult *= d_n
                    return d_ok, taken, pat

                ssd_okv, taken_ssd, pat_s = fit_dev(
                    sc.ds, sc.sv, "ssd_cap_s", ssdu_s, "ssd_mi", 1
                )
                hdd_okv, taken_hdd, pat_h = fit_dev(
                    sc.dh, sc.hv, "hdd_cap_s", hddu_s, "hdd_mi",
                    sc.ds ** sc.sv,
                )
                pat_dev = pat_s + pat_h
                has_s = srf["has_store"][:] != 0
                store_ok = has_s & lvm_ok & ssd_okv & hdd_okv
                feas = feas & (store_ok | (wants_s == 0))

            # ---- inter-pod affinity + topology spread ----
            # Eval reads state directly: count/pref state is zero at
            # nodes whose topology key is missing (init masked, commits
            # eq-gated), and inactive slots carry zero scalars, so no
            # per-node key mask is needed.
            if tc is not None and tc.has_ipa:
                fail_exist = jnp.zeros(shape, bool)
                fail_own = jnp.zeros(shape, bool)
                ipa_raw = jnp.zeros(shape, jnp.int32)
                for k in range(tc.rmax):
                    ci = tr["e_cnt"][u * tc.rmax + k]
                    tgtk = tgt_s[jnp.maximum(ci, 0)] * (ci >= 0)
                    pi = tr["e_pref"][u * tc.rmax + k]
                    pa = tr["e_panti"][u * tc.rmax + k]
                    pv = (pi >= 0).astype(jnp.int32)
                    pix = jnp.maximum(pi, 0)
                    pax = jnp.maximum(pa, 0)
                    ipa_raw = (
                        ipa_raw
                        + tr["e_cpd"][u * tc.rmax + k] * tgtk
                        + (pref_s[pix] - panti_s[pax]) * pv
                    )
                    ab = tr["e_antib"][u * tc.rmax + k]
                    fail_exist = fail_exist | (
                        (antib_s[tr["e_antip"][u * tc.rmax + k]] & ab) != 0
                    )
                    tb = tr["e_tposb"][u * tc.rmax + k]
                    fail_own = fail_own | (
                        (tposb_s[tr["e_tposp"][u * tc.rmax + k]] & tb) != 0
                    )

                # satisfyPodAffinity: required-affinity groups
                gid = gid_ref[u]
                keys_ok = jnp.ones(shape, bool)
                pods_exist = jnp.ones(shape, bool)
                total_g = jnp.zeros((), jnp.int32)
                for k in range(tc.gmax):
                    a_k = sgrows_ref[u * tc.gmax + k]
                    gv = a_k >= 0
                    ak = jnp.maximum(a_k, 0)
                    gvals = gtopo_ref[ak]
                    hasg = gvals >= 0
                    gck = jnp.where(hasg, group_s[ak], 0)
                    keys_ok = keys_ok & (hasg | ~gv)
                    pods_exist = pods_exist & ((gck > 0) | ~gv)
                    tot_k = jnp.sum(gtot_s[ak, 0:1, 0:1])
                    total_g = total_g + jnp.where(gv, tot_k, 0)
                self_ok = selfok_ref[u] != 0
                bootstrap = (total_g == 0) & self_ok
                aff_ok = (gid < 0) | (keys_ok & (pods_exist | bootstrap))
                feas = feas & aff_ok & ~fail_own & ~fail_exist

            if tc is not None and tc.has_hard:
                for k in range(tc.hmax):
                    ti = tr["h_topo"][u * tc.hmax + k]
                    hv = ti >= 0
                    hvals = topo_ref[jnp.maximum(ti, 0)]
                    cand = (cand_ref[jnp.maximum(tr["h_cand"][u * tc.hmax + k], 0)] != 0) & valid
                    counts = tgt_s[jnp.maximum(tr["h_cnt"][u * tc.hmax + k], 0)]
                    minc = jnp.min(jnp.where(cand, counts, BIG))
                    minc = jnp.where(jnp.any(cand), minc, 0)
                    cnt_eff = jnp.where(cand & (hvals >= 0), counts, 0)
                    selfm = tr["h_selfm"][u * tc.hmax + k]
                    skew = cnt_eff + selfm - minc
                    maxskew = tr["h_skew"][u * tc.hmax + k]
                    ok_c = (skew <= maxskew) & (hvals >= 0)
                    feas = feas & (ok_c | ~hv)

            # ---- scores ----
            # LeastAllocated (least_allocated.go:108-117)
            totc = st_nzc + nzc
            totm = st_nzm + nzm
            ok_c = (alloc_c > 0) & (totc <= alloc_c)
            ok_m = (alloc_nzm > 0) & (totm <= alloc_nzm)
            least_c = jnp.where(
                ok_c, (alloc_c - totc) * MAX_SCORE // jnp.maximum(alloc_c, 1), 0
            )
            least_m = jnp.where(
                ok_m, (alloc_nzm - totm) * MAX_SCORE // jnp.maximum(alloc_nzm, 1), 0
            )
            total = base_ref[bu] + ((least_c + least_m) // 2) * w_least

            if w_bal:
                # BalancedAllocation: fractions are exact in f32 (inputs
                # < 2^24); only the final truncation is float
                cpu_frac = totc.astype(jnp.float32) / jnp.maximum(alloc_c_f, 1.0)
                cpu_frac = jnp.where(alloc_c > 0, cpu_frac, 1.0)
                mem_frac = totm.astype(jnp.float32) / jnp.maximum(alloc_nzm_f, 1.0)
                mem_frac = jnp.where(alloc_nzm > 0, mem_frac, 1.0)
                balanced = jnp.where(
                    (cpu_frac >= 1.0) | (mem_frac >= 1.0),
                    0,
                    ((1.0 - jnp.abs(cpu_frac - mem_frac)) * MAX_SCORE).astype(
                        jnp.int32
                    ),
                )
                total = total + balanced * w_bal

            if w_simon:
                raw = simon_ref[su]
                hi = jnp.max(jnp.where(feas, raw, NEG))
                lo = jnp.min(jnp.where(feas, raw, BIG))
                rng = hi - lo
                sim = jnp.where(
                    rng > 0, (raw - lo) * MAX_SCORE // jnp.maximum(rng, 1), 0
                )
                total = total + sim * w_simon

            if w_na and has_nodeaff:
                raw = na_ref[clsmap_ref[3 * u_n + u]]
                mx = jnp.max(jnp.where(feas, raw, 0))
                na = jnp.where(mx > 0, MAX_SCORE * raw // jnp.maximum(mx, 1), 0)
                total = total + na * w_na

            if w_tt and has_taint:
                raw = tt_ref[clsmap_ref[4 * u_n + u]]
                mx = jnp.max(jnp.where(feas, raw, 0))
                base = jnp.where(mx > 0, MAX_SCORE * raw // jnp.maximum(mx, 1), 0)
                tt = jnp.where(mx > 0, MAX_SCORE - base, MAX_SCORE)
                total = total + tt * w_tt

            if tc is not None and tc.has_ipa and w_ipa:
                # InterPodAffinity NormalizeScore (scoring.go:246-270):
                # integer division reproduces the f64-truncate result for
                # these magnitudes (|numerator| < 2^31, denominator >= 1)
                mxi = jnp.maximum(jnp.max(jnp.where(feas, ipa_raw, 0)), 0)
                mni = jnp.minimum(jnp.min(jnp.where(feas, ipa_raw, 0)), 0)
                diff = mxi - mni
                ipa_sc = jnp.where(
                    diff > 0,
                    (MAX_SCORE * (ipa_raw - mni)) // jnp.maximum(diff, 1),
                    0,
                )
                total = total + ipa_sc * w_ipa

            if tc is not None and tc.has_soft and w_spread:
                # PodTopologySpread soft score (scoring.go). The XLA path
                # computes cnt*log(sz+2) in f64; f64 is unavailable here,
                # so the product runs in double-single f32 (split tables
                # w_h1/w_h2/w_lo, exact partial products, 2Sum chains) —
                # ~2^-45 relative error, then integer truncation.
                if stream:
                    hkeys = gath_s[tr["hk_pos"][u]] != 0
                else:
                    hkeys = haskeys_ref[clsmap_ref[5 * u_n + u]] != 0
                eligible = feas & hkeys
                acc_hi = jnp.zeros(shape, jnp.float32)
                acc_lo = jnp.zeros(shape, jnp.float32)
                any_svalid = jnp.zeros((), bool)
                for k in range(tc.smax):
                    sti = tr["s_topo_i"][u * tc.smax + k]
                    sv = sti >= 0
                    any_svalid = any_svalid | sv
                    svals = topo_ref[jnp.maximum(sti, 0)]
                    is_host = tr["s_ishost"][u * tc.smax + k] != 0
                    sz_host = jnp.sum((eligible).astype(jnp.int32))
                    sz_nh = jnp.zeros((), jnp.int32)
                    for v in range(tc.vs):
                        sz_nh = sz_nh + jnp.any(eligible & (svals == v)).astype(
                            jnp.int32
                        )
                    sz = jnp.where(is_host, sz_host, sz_nh)

                    def wval(ref, idx=sz):
                        # (Wr, 128) f32 VMEM table read at a traced
                        # scalar index: dynamic sublane row + lane mask
                        # (same pattern as pod_scalar)
                        row = ref[pl.ds(idx // LANES, 1), :]
                        return jnp.sum(
                            jnp.where(lane_iota == idx % LANES, row, 0.0)
                        )

                    whi = wval(whi_ref)
                    wlo = wval(wlo_ref)
                    wh1 = wval(wh1_ref)
                    wh2 = wval(wh2_ref)
                    ci_s = tr["s_cnt"][u * tc.smax + k]
                    cnt_host = tgt_s[jnp.maximum(ci_s, 0)]
                    cnt_soft = soft_s[jnp.maximum(tr["s_nh"][u * tc.smax + k], 0)]
                    cnt = jnp.where(is_host, cnt_host, cnt_soft) * (
                        svals >= 0
                    ).astype(jnp.int32)
                    c2 = cnt % 256
                    c1 = (cnt - c2).astype(jnp.float32)
                    c2f = c2.astype(jnp.float32)
                    # exact partial products (<=21-bit each)
                    hi_p, e1 = two_sum(c1 * wh1, c1 * wh2)
                    hi_p, e2 = two_sum(hi_p, c2f * wh1)
                    hi_p, e3 = two_sum(hi_p, c2f * wh2)
                    lo_p = e1 + e2 + e3 + cnt.astype(jnp.float32) * wlo
                    skew_k = tr["s_skewm1"][u * tc.smax + k].astype(jnp.float32)
                    hi_p, e4 = two_sum(hi_p, skew_k)
                    lo_p = lo_p + e4
                    hi_p = jnp.where(sv, hi_p, 0.0)
                    lo_p = jnp.where(sv, lo_p, 0.0)
                    acc_hi, e5 = two_sum(acc_hi, hi_p)
                    acc_lo = acc_lo + e5 + lo_p
                # truncate acc_hi + acc_lo toward zero (scores >= 0)
                base_f = jnp.floor(acc_hi)
                frac = (acc_hi - base_f) + acc_lo
                adj = jnp.where(frac >= 1.0, 1, jnp.where(frac < 0.0, -1, 0))
                raw_s = base_f.astype(jnp.int32) + adj
                validm = feas & hkeys
                anyv = jnp.any(validm)
                mxs = jnp.max(jnp.where(validm, raw_s, -BIG))
                mns = jnp.min(jnp.where(validm, raw_s, BIG))
                norm_s = jnp.where(
                    mxs == 0,
                    MAX_SCORE,
                    (MAX_SCORE * (mxs + mns - raw_s)) // jnp.maximum(mxs, 1),
                )
                soft_sc = jnp.where(validm, norm_s, 0)
                soft_sc = jnp.where(anyv, soft_sc, 0)
                soft_sc = jnp.where(any_svalid, soft_sc, MAX_SCORE)
                total = total + soft_sc * w_spread
            elif w_spread:
                # no soft constraints anywhere: NormalizeScore's
                # no-constraint branch is MaxNodeScore on every node — a
                # constant that cannot change the argmax; omitted
                pass

            if sc is not None and w_ol:
                # Open-Local raw score: host-f64 table value at (class,
                # storage row, assignment pattern), then the same
                # min-max normalize as Simon (scan.py _minmax_normalize)
                raw_st = jnp.zeros(shape, jnp.int32)
                srow = srf["storow"][:]
                for s_i in range(sc.sd):
                    srm = srow == s_i
                    base_l = (u * sc.sd + s_i) * sc.plvm
                    for p in range(sc.plvm):
                        msk = srm & (pat_lvm == p)
                        raw_st = raw_st + jnp.where(
                            msk, srf["lvm_sc"][base_l + p], 0
                        )
                    base_d = (u * sc.sd + s_i) * sc.pdev
                    for q in range(sc.pdev):
                        msk = srm & (pat_dev == q)
                        raw_st = raw_st + jnp.where(
                            msk, srf["dev_sc"][base_d + q], 0
                        )
                raw_st = jnp.where(has_s & (wants_s != 0), raw_st, 0)
                hi_st = jnp.max(jnp.where(feas, raw_st, NEG))
                lo_st = jnp.min(jnp.where(feas, raw_st, BIG))
                rng_st = hi_st - lo_st
                ol_sc = jnp.where(
                    rng_st > 0,
                    (raw_st - lo_st) * MAX_SCORE // jnp.maximum(rng_st, 1),
                    0,
                )
                total = total + ol_sc * w_ol

            masked = jnp.where(feas, total, NEG)
            m = jnp.max(masked)
            found = m > NEG
            cand = jnp.where(feas & (masked == m), idx_mat, BIG)
            best = jnp.min(cand)

            place = jnp.where(found, best, -1)
            if has_pins:
                # spec.nodeName overrides selection regardless of
                # feasibility (scan.py: pinned pods commit as forced
                # placements); a pin outside node_valid is INACTIVE
                pin = pod_scalar(7)
                pinc = jnp.maximum(pin, 0)
                vrow = valid_ref[pl.ds(pinc // LANES, 1), :]
                pin_ok = (
                    jnp.sum(jnp.where(lane_iota == pinc % LANES, vrow, 0)) != 0
                )
                place = jnp.where(
                    pin >= 0, jnp.where(pin_ok, pin, INACTIVE), place
                )
            place = jnp.where(active != 0, place, INACTIVE)
            # dynamic lane-dim stores are unsupported on TPU: rewrite
            # only the pod's 128-lane row, lane-selected via the mask
            prow = place_ref[pl.ds(pr, 1), :]
            place_ref[pl.ds(pr, 1), :] = jnp.where(lane, place, prow)
            if pk:
                # PostFilter: a failed pod allowed to preempt runs the
                # dry run; it rewrites the state, the slots and its rows
                def pre_scalar(s):
                    return jnp.sum(jnp.where(lane, pre_pod_ref[s, pl.ds(pr, 1), :], 0))

                for ref, v in ((pre_node_ref, -1), (pre_vic_ref, 0)):
                    row = ref[pl.ds(pr, 1), :]
                    ref[pl.ds(pr, 1), :] = jnp.where(lane, v, row)
                pre_prio = pre_scalar(0)

                @pl.when((place == -1) & (pre_scalar(1) != 0))
                def _():
                    dry_run(pr, lane, pre_prio, rc, rm, re, has_req,
                            feas_ref[fu] != 0, used_c, used_m, used_e,
                            st_nzc, st_nzm, pod_cnt)

                place = jnp.sum(jnp.where(lane, place_ref[pl.ds(pr, 1), :], 0))
                used_c = st_c_ref[:]
                used_m = st_m_ref[:]
                used_e = st_e_ref[:]
                st_nzc = st_nzc_ref[:]
                st_nzm = st_nzm_ref[:]
                pod_cnt = st_p_ref[:]

            do = place >= 0
            sel = (idx_mat == place) & do
            st_c_ref[:] = used_c + jnp.where(sel, rc, 0)
            st_m_ref[:] = used_m + jnp.where(sel, rm, 0)
            st_e_ref[:] = used_e + jnp.where(sel, re, 0)
            st_nzc_ref[:] = st_nzc + jnp.where(sel, nzc, 0)
            st_nzm_ref[:] = st_nzm + jnp.where(sel, nzm, 0)
            st_p_ref[:] = pod_cnt + jnp.where(sel, 1, 0)
            if pk:
                seq = flag_s[1]
                record_commit(sel, pod_cnt, {
                    "valid": 1, "hard": pre_scalar(2), "prio": pre_prio,
                    "seq": seq, "mcpu": rc, "mem": rm, "eph": re,
                    "nz_mcpu": nzc, "nz_mem": nzm,
                })
                flag_s[1] = seq + do.astype(jnp.int32)
            if s_n or pw:
                sel_i = sel.astype(jnp.int32)
            if s_n:
                for s in range(s_n):
                    uscal_s[s] = uscal_s[s] + reqscal_ref[u * s_n + s] * sel_i
            if g_n:
                # charge the chosen devices at the placed node only
                # (scan.py commit: gpu_used += onehot * take * gpu_mem[u])
                for g in range(g_n):
                    single_take = (
                        (gpu_best_dev == g) & gpu_fits_any
                    ).astype(jnp.int32)
                    multi_take = jnp.clip(gc - gpu_prefix[g], 0, gpu_caps[g])
                    take_g = jnp.where(gc == 1, single_take, multi_take)
                    charge = jnp.where(needs_gpu, take_g * gm, 0)
                    ugpu_s[g] = ugpu_s[g] + jnp.where(sel, charge, 0)
            if pw:
                for w_i in range(pw):
                    ports_pl[w_i] = ports_pl[w_i] | (
                        wantw_ref[u * pw + w_i] * sel_i
                    )
            if sc is not None:
                # commit the hypothetical allocation at the placed node
                # (scan.py: vg_used += onehot*vg_take, ssd/hdd_used |=
                # onehot & take)
                for j in range(sc.v):
                    vgu_s[j] = vgu_s[j] + jnp.where(sel, take_vg[j], 0)
                for d in range(sc.ds):
                    ssdu_s[d] = jnp.where(sel & taken_ssd[d], 1, ssdu_s[d])
                for d in range(sc.dh):
                    hddu_s[d] = jnp.where(sel & taken_hdd[d], 1, hddu_s[d])

            if tc is not None:
                inc = do.astype(jnp.int32)
                nr = jnp.where(do, place // LANES, 0)
                nc = jnp.where(do, place % LANES, 0)
                lane_nc = (lane_iota == nc)[None, :, :]  # (1, 1, C)
                lane_nc2 = lane_iota == nc  # (1, C) for 2D slabs
                lane_u3 = lane_iota == u % LANES  # (1, LANES)

                def col_u(tab_ref):
                    """Class-u column of a (X, Ur_p, 128) table ->
                    (X, 1, 1) i32 (dynamic sublane row u//128, lane
                    u%128 by mask — same pattern as pod_scalar)."""
                    slab = tab_ref[:, pl.ds(u // LANES, 1), :]
                    return jnp.sum(
                        jnp.where(lane_u3, slab, 0), axis=2, keepdims=True
                    )

                def val_at(t3_ref):
                    """(X, R, C) tile values at the placed node -> (X, 1, 1)."""
                    colslab = t3_ref[:, pl.ds(nr, 1), :]  # (X, 1, C)
                    return jnp.sum(
                        jnp.where(lane_nc, colslab, 0), axis=2, keepdims=True
                    )

                def val_at_row(t3_ref, idx):
                    """Row idx of a (X, R, C) tile at the placed node -> scalar."""
                    slab = t3_ref[idx, pl.ds(nr, 1), :]  # (1, C)
                    return jnp.sum(jnp.where(lane_nc2, slab, 0))

                # SPARSE commit: each class updates at most cmax
                # (row, topo) slots — count rows as += increments, bit
                # rows as monotone ORs. Inactive slots multiply to zero
                # (their read-modify-write of row 0 adds 0).
                for j in range(tc.cmax):
                    ti = tr["c_topo"][u * tc.cmax + j]
                    tix = jnp.maximum(ti, 0)
                    tvals = topo_ref[tix]
                    valt = val_at_row(topo_ref, tix)
                    upd = (
                        (tvals == valt) & (valt >= 0) & (ti >= 0)
                    ).astype(jnp.int32) * inc
                    ci = tr["c_cnt"][u * tc.cmax + j]
                    cix = jnp.maximum(ci, 0)
                    tgt_s[cix] = tgt_s[cix] + tr["c_m"][u * tc.cmax + j] * upd * (ci >= 0)
                    if tc.has_ipa:
                        pi2 = tr["c_pref"][u * tc.cmax + j]
                        pa2 = tr["c_panti"][u * tc.cmax + j]
                        pix = jnp.maximum(pi2, 0)
                        pax = jnp.maximum(pa2, 0)
                        pfac = upd * (pi2 >= 0)
                        pref_s[pix] = pref_s[pix] + tr["c_prefc"][u * tc.cmax + j] * pfac
                        panti_s[pax] = panti_s[pax] + tr["c_pantic"][u * tc.cmax + j] * pfac
                        ap = tr["c_antip"][u * tc.cmax + j]
                        antib_s[ap] = antib_s[ap] | (tr["c_antib"][u * tc.cmax + j] * upd)
                        tp_ = tr["c_tposp"][u * tc.cmax + j]
                        tposb_s[tp_] = tposb_s[tp_] | (tr["c_tposb"][u * tc.cmax + j] * upd)

                if tc.has_ipa:
                    g_valt = val_at(gtopo_ref)  # (A, 1, 1)
                    g_eq = ((gtopo_ref[:] == g_valt) & (g_valt >= 0)).astype(
                        jnp.int32
                    )
                    g_m = col_u(gmatch_ref)[: tc.a] * (g_valt >= 0)
                    group_s[:] = group_s[:] + (g_m * inc) * g_eq
                    gtot_s[:] = gtot_s[:] + g_m * inc
                if tc.has_soft:
                    for j in range(tc.scmax):
                        si = tr["sc_nh"][u * tc.scmax + j]
                        six = jnp.maximum(si, 0)
                        sti2 = jnp.maximum(tr["sc_topo"][u * tc.scmax + j], 0)
                        stvals = topo_ref[sti2]
                        s_valt = val_at_row(topo_ref, sti2)
                        s_q_at = (
                            val_at_row(sq_ref, jnp.maximum(tr["sc_q"][u * tc.scmax + j], 0))
                            != 0
                        )
                        s_upd = (
                            (stvals == s_valt)
                            & (s_valt >= 0)
                            & (si >= 0)
                            & s_q_at
                        ).astype(jnp.int32) * inc
                        soft_s[six] = soft_s[six] + tr["sc_m"][u * tc.scmax + j] * s_upd

            return u

        if stream:
            # flush the dirty rows of class `cu` back to HBM (no-op
            # when `valid` is False, i.e. before the first pod). The
            # waits double as the ordering barrier against the next
            # class's gather of the same rows.
            def _flush_class(cu, valid_c):
                for j in range(tc.wmax):
                    w_g = tr["wb_gid"][cu * tc.wmax + j]
                    w_p = tr["wb_pos"][cu * tc.wmax + j]

                    @pl.when(valid_c & (w_g >= 0))
                    def _(j=j, w_g=w_g, w_p=w_p):
                        pltpu_mod.make_async_copy(
                            gath_s.at[pl.ds(jnp.maximum(w_p, 0), 1)],
                            state_out_ref.at[pl.ds(w_g, 1)],
                            state_sem.at[j % _STREAM_NSEM],
                        ).start()
                for j in range(tc.wmax):
                    w_g = tr["wb_gid"][cu * tc.wmax + j]
                    w_p = tr["wb_pos"][cu * tc.wmax + j]

                    @pl.when(valid_c & (w_g >= 0))
                    def _(j=j, w_g=w_g, w_p=w_p):
                        pltpu_mod.make_async_copy(
                            gath_s.at[pl.ds(jnp.maximum(w_p, 0), 1)],
                            state_out_ref.at[pl.ds(w_g, 1)],
                            state_sem.at[j % _STREAM_NSEM],
                        ).wait()

        last_u = jax.lax.fori_loop(0, p_total, step, jnp.int32(-1))
        if sc is not None:
            # export the final VG usage (scaled) for the capacity
            # sweep's vg_util (decode_scan_output converts to bytes)
            vg_out_ref[:] = vgu_s[:]
        if stream:
            # the final class's commits live only in scratch until here
            _flush_class(jnp.maximum(last_u, 0), last_u >= 0)

    return kernel


class _Compiled(NamedTuple):
    fn: object


_COMPILED_CACHE: dict = {}

# device-resident copies of a plan's (numpy) arrays: every per-call
# host->device transfer has a fixed cost (a terms plan holds ~55
# arrays), so transfer once per plan. Keyed by
# id(plan) with a strong ref pinning it (utils/memo.py contract).
# LRU-ordered: hits move-to-end so eviction under >16 live plans
# (concurrent sweeps) targets the coldest plan, not the hot one.
_DEVICE_PLAN_CACHE: "OrderedDict" = OrderedDict()

# host-packed scenario-invariant pod-scalar rows, same identity contract
_POD_SCAL_CACHE: "OrderedDict" = OrderedDict()

# both caches pin finished plans (host numpy + device buffers) until
# eviction; release them with the memos at the planner boundary
from ..utils.memo import register_cache as _register_cache  # noqa: E402

_register_cache(_DEVICE_PLAN_CACHE.clear)
_register_cache(_POD_SCAL_CACHE.clear)


def _plan_args_np(plan: PallasPlan) -> list:
    """The plan's kernel-input arrays, in ref order (host numpy)."""
    args = [
        plan.clsmap,
        plan.alloc_mcpu, plan.alloc_mem_s, plan.alloc_eph_s, plan.alloc_pods,
        plan.alloc_nzmem_s,
        plan.static_feasible, plan.simon_raw,
    ]
    if plan.has_nodeaff:
        args.append(plan.nodeaff_raw)
    if plan.has_taint:
        args.append(plan.taint_intol)
    args += [
        plan.base_score,
        plan.init_used_mcpu, plan.init_used_mem_s, plan.init_used_eph_s,
        plan.init_nz_mcpu, plan.init_nz_mem_s, plan.init_pod_cnt,
    ]
    if plan.s_n:
        args += [plan.alloc_scal, plan.iscal0, plan.req_scal]
    if plan.g_n:
        args += [
            plan.gpu_per_dev, plan.gpu_cnt_n, plan.gpu_tot,
            plan.igpu0, plan.gpu_mem_u, plan.gpu_cnt_u,
        ]
    if plan.pw:
        args += [plan.ports0, plan.want_w, plan.confl_w]
    if plan.store is not None:
        args += [getattr(plan.store, name) for name, _ in _STORE_FIELDS]
    if plan.pre is not None:
        args += [plan.pre.table0, plan.pre.pod, plan.pre.meta]
    if plan.terms is not None:
        fields = (
            _STREAM_TERM_FIELDS
            if plan.terms.cfg.stream
            else _TERM_FIELDS
        )
        args += [getattr(plan.terms, name) for name, _ in fields]
    return args


def _plan_metas(args: list) -> tuple:
    """(shape, dtype) layout of the packed plan buffer — part of the
    compiled-call cache key (dedup-table row counts vary per plan even
    at one TermsCfg, so the layout is not derivable from the cfg)."""
    return tuple((a.shape, str(np.asarray(a).dtype)) for a in args)


def _unpack_flat(flat, metas, off=None):
    """Traced inverse of the host-side pack: slice/reshape/bitcast the
    single flat int32 buffer back into the kernel's input arrays.
    Runs INSIDE the compiled call so the slices fuse into the one XLA
    program — no intermediate device buffers materialize (each
    buffer a call touches has a fixed transfer cost). With
    `off` (a traced scalar) the plan sits at a dynamic offset inside a
    GROUP buffer holding many plans (preload_plan_group)."""
    import jax.numpy as jnp
    from jax import lax

    outs = []
    o = 0
    for shape, dt in metas:
        n = int(np.prod(shape)) if shape else 1
        if off is None:
            seg = flat[o : o + n]
        else:
            seg = lax.dynamic_slice_in_dim(flat, off + o, n)
        seg = seg.reshape(shape)
        if dt == "float32":
            seg = lax.bitcast_convert_type(seg, jnp.float32)
        outs.append(seg)
        o += n
    return outs


def preload_plan_group(plans: list) -> None:
    """Ship MANY plans' packed buffers in ONE host->device transfer:
    the group concatenates into a single flat array, and each plan's
    cache entry records its offset — the compiled call then slices at
    a traced offset (_unpack_flat off). A multi-spec what-if's first
    round otherwise pays one host->device transfer per plan."""
    import jax

    entries = []
    flats = []
    o = 0
    for plan in plans:
        hit = _DEVICE_PLAN_CACHE.get(id(plan))
        if (hit is not None and hit[0] is plan) or any(
            e[0] is plan for e in entries
        ):
            continue  # already shipped
        args = _plan_args_np(plan)
        metas = _plan_metas(args)
        flat = np.concatenate(
            [np.ascontiguousarray(a).view(np.int32).reshape(-1) for a in args]
        )
        entries.append((plan, o, metas))
        flats.append(flat)
        o += int(flat.size)
    if not flats:
        return
    big = np.concatenate(flats)
    with jax.enable_x64(False):
        big_dev = jax.device_put(big)
    # insert the whole group first, THEN trim: per-insert eviction
    # could evict this group's own earlier entries when the group
    # exceeds the cap (freeing nothing — they share one buffer) and
    # silently re-serialize those plans' transfers
    for plan, off, metas in entries:
        _DEVICE_PLAN_CACHE[id(plan)] = (plan, (big_dev, off), metas)
    while len(_DEVICE_PLAN_CACHE) > max(16, len(entries)):
        _DEVICE_PLAN_CACHE.popitem(last=False)


def _device_args(plan: PallasPlan):
    """The plan's packed device buffer (ONE flat int32 array, ONE
    host->device transfer, cached per plan) plus its layout metas."""
    import jax

    hit = _DEVICE_PLAN_CACHE.get(id(plan))
    if hit is not None and hit[0] is plan:
        _DEVICE_PLAN_CACHE.move_to_end(id(plan))
        return hit[1], hit[2]
    args = _plan_args_np(plan)
    metas = _plan_metas(args)
    flat = np.concatenate(
        [np.ascontiguousarray(a).view(np.int32).reshape(-1) for a in args]
    )
    with jax.enable_x64(False):
        dev = jax.device_put(flat)
    if len(_DEVICE_PLAN_CACHE) >= 16:
        # evict the least-recently-used entry; a wholesale clear would
        # drop the device copies of plans still in active use
        _DEVICE_PLAN_CACHE.popitem(last=False)
    _DEVICE_PLAN_CACHE[id(plan)] = (plan, dev, metas)
    return dev, metas

# None = auto (use the kernel only on a real TPU backend — the Pallas
# interpreter would crawl at bench scale on CPU); tests set True to
# exercise the integration paths under interpret mode
FORCE_ENABLE: Optional[bool] = None


def kernel_label(plan: "PallasPlan") -> str:
    """The trace/bench label for a built plan — one definition so the
    engine's batch-kernel note and the bench's backend tag can never
    disagree about which kernel layout ran."""
    if plan.terms is not None and plan.terms.cfg.stream:
        return "pallas-stream"
    return "pallas"


def should_use() -> bool:
    """Whether eligible callers should run the fused kernel."""
    if FORCE_ENABLE is not None:
        return FORCE_ENABLE
    import jax

    return jax.default_backend() == "tpu"


def kernel_call(plan: PallasPlan, p_total: int, metas: tuple,
                grouped: bool = False, interpret: bool = False):
    """The jitted fused-kernel call for one plan layout, cached per
    layout: ``pod_scan_fused(percall, flat_plan)`` over the two packed int32
    buffers (percall: 8 pod-scalar rows, the active row and the node
    validity row, plus a trailing group offset when ``grouped``;
    flat_plan: the plan arrays of ``metas``). Built from shapes alone,
    so it can be lowered from ``ShapeDtypeStruct``s for a described
    chip (tests/test_tpu_compile.py). Trace it with x64 off."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pr_rows = _pr_rows(p_total)
    tc = plan.terms.cfg if plan.terms is not None else None
    sc = plan.store.cfg if plan.store is not None else None
    pk = plan.pre.k if plan.pre is not None else 0
    key = (p_total, plan.r, plan.u, plan.w, plan.has_nodeaff, plan.has_taint,
           plan.has_pins, plan.s_n, plan.g_n, plan.pw, sc, tc, pk, metas,
           grouped, interpret)
    cached = _COMPILED_CACHE.get(key)
    if cached is not None:
        return cached.fn
    kernel = _make_kernel(p_total, plan.u, plan.w, plan.has_nodeaff,
                          plan.has_taint, plan.has_pins, plan.s_n,
                          plan.g_n, plan.pw, sc, tc, pk)
    rc = (plan.r, LANES)
    base_n = (
        18 + int(plan.has_nodeaff) + int(plan.has_taint)
        + (3 if plan.s_n else 0) + (6 if plan.g_n else 0)
        + (3 if plan.pw else 0)
        + (len(_STORE_FIELDS) if sc is not None else 0)
        + (3 if pk else 0)
    )
    stream = tc is not None and tc.stream
    term_fields = _STREAM_TERM_FIELDS if stream else _TERM_FIELDS
    n_in = base_n + (len(term_fields) if tc is not None else 0)
    # memory spaces: clsmap (base idx 3) in SMEM; the scalar/port
    # blocks sit at the end of the base args (alloc VMEM, init ANY,
    # tables SMEM); term-block spaces come from _TERM_FIELDS
    smem_idx = {3}
    any_idx = set()
    off = 18 + int(plan.has_nodeaff) + int(plan.has_taint)
    if plan.s_n:
        any_idx.add(off + 1)  # iscal0
        smem_idx.add(off + 2)  # req_scal
        off += 3
    if plan.g_n:
        any_idx.add(off + 3)  # igpu0
        smem_idx.update((off + 4, off + 5))  # gpu_mem_u / gpu_cnt_u
        off += 6
    if plan.pw:
        any_idx.add(off)  # ports0
        smem_idx.update((off + 1, off + 2))  # want/conflict words
        off += 3
    if sc is not None:
        for soff, (_, space) in enumerate(_STORE_FIELDS):
            if space == "any":
                any_idx.add(off + soff)
            elif space == "smem":
                smem_idx.add(off + soff)
        off += len(_STORE_FIELDS)
    if pk:
        any_idx.add(off)  # table0
        smem_idx.add(off + 2)  # next commit sequence
        off += 3
    if tc is not None:
        for toff, (_, space) in enumerate(term_fields):
            if space == "any":
                any_idx.add(base_n + toff)
            elif space == "smem":
                smem_idx.add(base_n + toff)

    scratch = []
    if plan.s_n or plan.g_n or plan.pw or sc is not None or tc is not None or pk:
        from jax.experimental.pallas import tpu as _pltpu

        rl = (plan.r, LANES)
        if plan.s_n:
            scratch.append(_pltpu.VMEM((plan.s_n,) + rl, jnp.int32))
        if plan.g_n:
            scratch.append(_pltpu.VMEM((plan.g_n,) + rl, jnp.int32))
        if plan.pw:
            scratch.append(_pltpu.VMEM((plan.pw,) + rl, jnp.int32))
        if sc is not None:
            scratch += [
                _pltpu.VMEM((sc.v,) + rl, jnp.int32),  # vg used
                _pltpu.VMEM((sc.ds,) + rl, jnp.int32),  # ssd used
                _pltpu.VMEM((sc.dh,) + rl, jnp.int32),  # hdd used
            ]
        if tc is not None:
            if stream:
                scratch += [
                    _pltpu.VMEM((tc.a,) + rl, jnp.int32),  # group
                    _pltpu.VMEM((tc.a, SUBLANES, LANES), jnp.int32),
                    _pltpu.VMEM((tc.kmax,) + rl, jnp.int32),  # gather
                    _pltpu.SemaphoreType.DMA((_STREAM_NSEM,)),
                ]
            else:
                scratch += [
                    _pltpu.VMEM((tc.tc,) + rl, jnp.int32),  # tgt counts
                    _pltpu.VMEM((tc.tp,) + rl, jnp.int32),  # pref (combined)
                    _pltpu.VMEM((tc.tp,) + rl, jnp.int32),  # panti
                    _pltpu.VMEM((tc.bp,) + rl, jnp.int32),  # anti>0 bitplanes
                    _pltpu.VMEM((tc.bp,) + rl, jnp.int32),  # tgt>0 bitplanes
                    _pltpu.VMEM((tc.a,) + rl, jnp.int32),  # group
                    _pltpu.VMEM((tc.a, SUBLANES, LANES), jnp.int32),  # gtot
                    _pltpu.VMEM((tc.csn,) + rl, jnp.int32),  # soft non-host
                ]
        if pk:
            scratch += [
                _pltpu.VMEM((len(_PRE_FIELDS) * pk,) + rl, jnp.int32),  # slots
                _pltpu.SMEM((2,), jnp.int32),  # overflow flag, next sequence
            ]
        scratch.append(_pltpu.SemaphoreType.DMA)

    n_ps = 8 * pr_rows * LANES
    n_act = pr_rows * LANES
    n_val = plan.r * LANES

    # the name is the kernel's in a profiler trace: module
    # `jit_pod_scan_fused`, Pallas call `pod_scan_fused`
    @jax.jit
    def pod_scan_fused(percall, flat_plan):
        # both the per-call inputs and the plan ship as ONE packed
        # buffer each; the slices fuse into this program
        # (_unpack_flat) so no per-array device buffers ever
        # materialize. Grouped plans add their offset as
        # the trailing percall element.
        off = percall[n_ps + n_act + n_val] if grouped else None
        arrays = [
            percall[:n_ps].reshape(8, pr_rows, LANES),
            percall[n_ps : n_ps + n_act].reshape(pr_rows, LANES),
            percall[n_ps + n_act : n_ps + n_act + n_val].reshape(
                plan.r, LANES
            ),
        ] + _unpack_flat(flat_plan, metas, off)

        def spec(i):
            if i in any_idx:
                return pl.BlockSpec(memory_space=pl.ANY)
            if i in smem_idx:
                return pl.BlockSpec(memory_space=pltpu.SMEM)
            return pl.BlockSpec(memory_space=pltpu.VMEM)
        out_shape = [
            jax.ShapeDtypeStruct((pr_rows, LANES), jnp.int32),
        ] + [jax.ShapeDtypeStruct(rc, jnp.int32) for _ in range(6)]
        out_specs = [
            pl.BlockSpec(memory_space=pltpu.VMEM) for _ in range(7)
        ]
        if sc is not None:
            # final VG usage (capacity vg_util)
            out_shape.append(
                jax.ShapeDtypeStruct((sc.v,) + rc, jnp.int32)
            )
            out_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        if stream:
            # the mutated term-state buffer stays in HBM (ANY) and
            # is never fetched; listing it as an output gives the
            # kernel a writable destination for the row DMAs
            out_shape.append(
                jax.ShapeDtypeStruct((tc.srows, plan.r, LANES), jnp.int32)
            )
            out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        if pk:
            # per pod: the preemption node (or NONE / ESCAPE) and the
            # victims' slots as a bit mask
            out_shape += [jax.ShapeDtypeStruct((pr_rows, LANES), jnp.int32)] * 2
            out_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2
        outs = pl.pallas_call(
            kernel,
            out_shape=tuple(out_shape),
            in_specs=[spec(i) for i in range(n_in)],
            out_specs=tuple(out_specs),
            scratch_shapes=scratch,
            interpret=interpret,
            name="pod_scan_fused",
        )(*arrays)
        # ONE output array (placements + 6 states + any VG usage
        # concatenated on the row axis): every host-blocking point
        # has a fixed cost regardless of size, so the whole call has
        # exactly one — the single fetch below
        fetched = list(outs[:7])
        if sc is not None:
            fetched.append(outs[7].reshape(sc.v * plan.r, LANES))
        if pk:
            fetched += list(outs[-2:])
        return jnp.concatenate(fetched, axis=0)

    _COMPILED_CACHE[key] = _Compiled(fn=pod_scan_fused)
    return pod_scan_fused


def run_scan_pallas(plan: PallasPlan, class_of_pod, pod_active, node_valid,
                    pinned=None, interpret=None, defer=False):
    """Run the fused scan. Returns (placements[P] np.int32, final used
    dict in TRUE units for utilization reporting). `pinned` ([P] node
    index or -1; required when the plan was built with pins) forces
    spec.nodeName placements. `interpret` forces the Pallas interpreter
    (None = auto: interpret off-TPU). With `defer=True` the raw DEVICE
    output array is returned unfetched, so a caller dispatching many
    scans (defrag depths) can stack them and pay the per-sync cost
    once; decode each row-block with decode_scan_output."""
    import jax

    p_total = int(np.asarray(class_of_pod).shape[0])
    # dense (Pr, 128) packing: a (P, 1) VMEM array would be lane-padded
    # 128x by the (8, 128) tile layout (51 MB at 100k pods)
    pr_rows = _pr_rows(p_total)
    p_pad = pr_rows * LANES
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    flat_dev, metas = _device_args(plan)
    grouped = isinstance(flat_dev, tuple)
    call = kernel_call(plan, p_total, metas, grouped, interpret)

    def pack(vec):
        out = np.zeros(p_pad, dtype=np.int32)
        out[:p_total] = vec
        return out.reshape(pr_rows, LANES)

    cls = np.asarray(class_of_pod, dtype=np.int32)
    # per-pod scalar rows: class + class-derived request scalars,
    # gathered host-side so the kernel never lane-indexes a class table;
    # row 7 carries the nodeName pin (-1 = loose). Rows 0-6 are
    # scenario-invariant — memoize per (plan, class array) so sweeps
    # that loop scenarios (defrag depths, capacity counts) pack once.
    memo_key = (id(plan), id(class_of_pod))
    hit = _POD_SCAL_CACHE.get(memo_key)
    if hit is not None and hit[0] is plan and hit[1] is class_of_pod:
        _POD_SCAL_CACHE.move_to_end(memo_key)
        pod_scal = hit[2].copy()
    else:
        pod_scal = np.zeros((8, pr_rows, LANES), dtype=np.int32)
        pod_scal[0] = pack(cls)
        for s in range(6):
            pod_scal[1 + s] = pack(plan.class_scalars[cls, s])
        if len(_POD_SCAL_CACHE) >= 16:
            _POD_SCAL_CACHE.popitem(last=False)
        _POD_SCAL_CACHE[memo_key] = (plan, class_of_pod, pod_scal.copy())
    if plan.has_pins:
        if pinned is None:
            raise ValueError("plan has pins: pass the pinned[] array")
        pin_vec = np.asarray(pinned, dtype=np.int32)
        pod_scal[7] = pack(np.where(pin_vec >= 0, pin_vec, -1))
    elif pinned is not None and (np.asarray(pinned) >= 0).any():
        raise ValueError("pinned pods but the plan was built without pins")
    active_2d = pack(np.asarray(pod_active).astype(np.int32))
    valid = _pad_nodes(np.asarray(node_valid).astype(np.int32), plan.r)

    # the engine enables x64 globally (ops/__init__.py) for the XLA
    # scan's int64 semantics, but this kernel is int32 by construction
    # and Mosaic's convert rules recurse on x64-promoted loop indices —
    # trace and run with x64 off
    with jax.enable_x64(False):
        # per-call inputs ride as ONE packed numpy buffer straight into
        # the dispatch: the implicit transfer pipelines with the
        # dispatch so the single np.asarray fetch is the call's only
        # sync point
        parts = [pod_scal.reshape(-1), active_2d.reshape(-1), valid.reshape(-1)]
        if grouped:
            flat_dev, off_v = flat_dev
            parts.append(np.array([off_v], dtype=np.int32))
        percall = np.concatenate(parts)
        out_d = call(percall, flat_dev)
        if defer:
            # caller batches several scans (e.g. defrag depths) and
            # fetches them stacked in ONE sync via decode_scan_output
            return out_d
        out = np.asarray(out_d)
    return decode_scan_output(plan, out, p_total)


def run_scan_pallas_batch(plan: PallasPlan, class_of_pod, scenarios):
    """Several scan scenarios with ONE device sync: each dispatches
    deferred, the outputs stack on the device, and one fetch pays the
    per-sync latency for all of them (defrag depths, paired
    capacity probes). `scenarios` is a list of (pod_active, node_valid,
    pinned) triples; returns [(placements, final), ...]. Keeping the
    dispatch/stack/decode protocol here means the kernel's output
    row-split contract has exactly one consumer module."""
    import jax.numpy as jnp

    outs = [
        run_scan_pallas(
            plan, class_of_pod, pod_active, node_valid, pinned=pin, defer=True
        )
        for pod_active, node_valid, pin in scenarios
    ]
    stacked = np.asarray(jnp.stack(outs))
    p_total = int(np.asarray(class_of_pod).shape[0])
    return [decode_scan_output(plan, row, p_total) for row in stacked]


def decode_scan_output(plan: PallasPlan, out: np.ndarray, p_total: int):
    """Split a fetched kernel output row-block into (placements, final
    used dict) — the tail of run_scan_pallas, exposed for deferred
    (stacked-fetch) callers."""
    pr_rows = _pr_rows(p_total)
    place = out[:pr_rows]
    states = out[pr_rows : pr_rows + 6 * plan.r]
    place = place.reshape(-1)[:p_total]
    # map padded slots: any placement index beyond n means "no node"
    place = np.where((place >= 0) & (place >= plan.n), -1, place)
    st = states.reshape(6, -1)[:, : plan.n].astype(np.int64)
    if plan.pre is not None:
        # the last two row blocks: preemption node and victim bit mask
        tail = out[out.shape[0] - 2 * pr_rows:].reshape(2, -1)[:, :p_total]
        bits = tail[1].astype(np.int64)
        pre_final = {
            "pre_node": tail[0].astype(np.int64),
            "victims": ((bits[:, None] >> np.arange(plan.pre.k)) & 1).astype(bool),
        }
    else:
        pre_final = {}
    final = {
        "used_mcpu": st[0],
        "used_mem": st[1] * plan.s_mem,
        "nz_mcpu": st[3],
        "nz_mem": st[4] * plan.s_nzmem,
        "pod_cnt": st[5],
        **pre_final,
    }
    if plan.store is not None:
        v = plan.store.cfg.v
        vg_rows = out[pr_rows + 6 * plan.r : pr_rows + (6 + v) * plan.r]
        # (V, R*C) scaled -> [N, V] bytes, the XLA final-state layout
        final["vg_used"] = (
            vg_rows.reshape(v, -1)[:, : plan.n].T.astype(np.int64)
            * plan.store.scale
        )
    return place, final
