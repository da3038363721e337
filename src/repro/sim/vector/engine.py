"""The vectorized simulation core: a fluid, bucketed fast path.

State is struct-of-arrays ``[cell, region]`` (cell = model x pool)
advanced in fixed ``dt`` buckets by ONE jitted ``lax.scan`` whose carry
is donated; many replicas step in lockstep under ``jax.vmap``.  The
Python control plane (hourly forecast/ILP/placement planners, scenario
outages) is untouched: the scan pauses at each control boundary, the
host reads aggregate signals out of the carry in the same shapes the
event loop feeds ``GlobalPlanner.plan``, and the resulting ``Plan`` is
applied back into array state before the scan resumes.

What is fluid here (and therefore approximate — see docs/PERF.md for
the tolerance contract): request flows are real-valued token/count
rates per bucket; per-request queueing delay is reconstructed from the
per-bucket queue-drain estimate the kernel emits.  What is exact:
instance counts and their acquisition delays (spot swap / local load /
remote fetch, as whole buckets), policy trigger logic, hourly plans,
placement actuation, outage windows, and determinism (pure array ops,
bit-identical across repeats).
"""
from __future__ import annotations

import concurrent.futures
import heapq
import math
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.api.capabilities import capability
from repro.api.plan import Plan, PlacementState
from repro.control.amortize import DEFAULT_CACHE as _SOLVE_CACHE
from repro.control.fleet import FleetForecast
from repro.control.forecast import fit_cache_stats
from repro.sim.metrics import Report
from repro.sim.perfmodel import PROFILES, PerfProfile
from repro.sim.simulator import SimConfig
from repro.sim.types import Request
from repro.sim.workload import Trace
from repro.sim.vector.buckets import BucketedTrace, bucketize
from repro.sim.vector.params import (MODE_CHIRON, MODE_LT, MODE_REACTIVE,
                                     LT_I, LT_UA, ReplicaParams,
                                     VectorUnsupported, extract, group_key)
from repro.sim.vector.report import ReplicaAccumulator

_EPS = 1e-9
_DRAIN_RING = 3   # scale-ins serve ~1 bucket before reaping to spot
# Every matmul of the step runs at HIGHEST precision: the TPU's default
# f32 matmul rounds its inputs to bf16 (8-bit mantissa), which would
# round token masses of ~1e4-1e6 per bucket by up to 0.4% each step.
# The operands are small ([M, C] one-hots, [C, J, J] routing fractions).
_HIGHEST = jax.lax.Precision.HIGHEST

#: carry keys the hourly control boundary *reads* (aggregate signals
#: fed to the planner) and the four it *writes* — the batched boundary
#: transfers exactly these slices instead of materializing the carry
_HOUR_READS = ("live", "ring", "dep", "wloc", "warm", "down")
_HOUR_WRITES = ("tgt", "fc", "omega", "has_om")


class _Static:
    """Per-group compile-time constants closed over by the step fn."""

    def __init__(self, models: List[str], regions: List[str],
                 pools: Tuple[str, ...],
                 profiles: Dict[str, PerfProfile], dt: float):
        self.models, self.regions, self.pools = models, regions, pools
        self.M, self.J, self.P = len(models), len(regions), len(pools)
        self.C = self.M * self.P
        self.dt = float(dt)
        per = lambda f: np.asarray([f(profiles[m])
                                    for m in models for _ in pools])
        self.kv = per(lambda p: float(p.kv_capacity_tokens))
        self.ptps = per(lambda p: p.prompt_tps)
        self.tbt0 = per(lambda p: p.base_tbt)
        self.alpha = per(lambda p: p.batch_alpha)
        self.mb = per(lambda p: float(p.max_batch))
        bk = lambda s: np.maximum(np.ceil(s / dt).astype(np.int32), 1)
        self.swap_b = bk(per(lambda p: p.spot_swap_time))
        self.local_b = bk(per(lambda p: p.load_time_local))
        self.remote_b = bk(per(lambda p: p.load_time_remote))
        self.L = int(max(self.swap_b.max(), self.local_b.max(),
                         self.remote_b.max())) + 1
        self.LD = _DRAIN_RING
        # pool->model one-hot (cells of one model share warm tags,
        # weights locality and deployment)
        self.pm = np.zeros((self.M, self.C))
        for mi in range(self.M):
            for p in range(self.P):
                self.pm[mi, mi * self.P + p] = 1.0
        self.cell_model = np.asarray(
            [mi for mi in range(self.M) for _ in pools])
        self.niw_pool = self.P - 1     # NIW lands in the last pool

    # reprolint: cache-key=__init__
    def key(self) -> Tuple:
        """Everything the traced computation closes over — two groups
        with equal keys share one compiled kernel.  The step closes
        over *counts* and numeric arrays, never name strings, so the
        key holds M/J/P rather than the labels: two fleets that differ
        only in model/region/pool names reuse the same kernel (the
        trace tier's T3 audit pins this — keying on names fragments
        ``_SEG_CACHE`` with byte-identical lowerings)."""
        # reprolint: key-exempt=models -- names are host-side labels; M is keyed
        # reprolint: key-exempt=regions -- names are host-side labels; J is keyed
        # reprolint: key-exempt=pools -- names are host-side labels; P is keyed
        # reprolint: key-exempt=C -- derived: C = M * P
        # reprolint: key-exempt=L -- derived from swap_b/local_b/remote_b maxima
        # reprolint: key-exempt=LD -- module constant _DRAIN_RING
        # reprolint: key-exempt=pm -- derived one-hot of (M, P)
        # reprolint: key-exempt=cell_model -- derived index map of (M, P)
        # reprolint: key-exempt=niw_pool -- derived: P - 1
        return (self.M, self.J, self.P, self.dt,
                self.kv.tobytes(), self.ptps.tobytes(),
                self.tbt0.tobytes(), self.alpha.tobytes(),
                self.mb.tobytes(), self.swap_b.tobytes(),
                self.local_b.tobytes(), self.remote_b.tobytes())


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def _build_step(st: _Static):
    C, J, L, LD, dt = st.C, st.J, st.L, st.LD, st.dt
    f32 = jnp.float32
    KV = jnp.asarray(st.kv, f32)[:, None]
    PTPS = jnp.asarray(st.ptps, f32)[:, None]
    TBT0 = jnp.asarray(st.tbt0, f32)[:, None]
    ALPHA = jnp.asarray(st.alpha, f32)[:, None]
    MB = jnp.asarray(st.mb, f32)[:, None]
    PM = jnp.asarray(st.pm, f32)            # [M, C]
    PMT = PM.T                              # [C, M]
    SWAP = jnp.asarray(st.swap_b)
    LOCAL = jnp.asarray(st.local_b)
    REMOTE = jnp.asarray(st.remote_b)
    CI = jnp.arange(C)
    CI3 = jnp.tile(CI, 3)
    PRI = np.asarray([[h] + [k for k in range(J) if k != h]
                      for h in range(J)])
    PRIJ = jnp.asarray(PRI)

    def step(prm, carry, x):
        b = x["b"]
        # -- 1. activate pending instances / reap drained ones ---------
        idx = jnp.mod(b, L)
        live = carry["live"] + carry["ring"][idx]
        ring = carry["ring"].at[idx].set(0.0)
        idx_d = jnp.mod(b, LD)
        reap = carry["drainq"][idx_d]
        drainq = carry["drainq"].at[idx_d].set(0.0)
        spot = carry["spot"] + reap.sum(axis=0)
        warm = carry["warm"] + _mm(PM, reap)
        draining = drainq.sum(axis=0)
        pend = ring.sum(axis=0)
        dep_c = _mm(PMT, carry["dep"])
        down = carry["down"]

        # -- 2. utilization (reserved incl. queued, like Endpoint.util)
        outst = carry["qp"] + carry["qo"] + carry["f_tok"]
        alive = live > 0.5
        u = jnp.where(alive,
                      jnp.clip(outst / jnp.maximum(KV * live, 1.0),
                               0.0, 1.0), 1.0)
        total = live + pend

        # -- 3. routing matrix Rm[c, home, dest] -----------------------
        score = jnp.where(alive, u,
                          jnp.where((dep_c > 0.5) & (down[None, :] < 0.5),
                                    1.5, 2.0))
        below = score < prm["route_thr"]
        fallback = jnp.argmin(score, axis=1)
        # per-home priority: home first, then regions ascending; pick
        # the first destination under threshold, else the best score
        bp = below[:, PRI]                       # [C, home, priority]
        first = PRIJ[jnp.arange(J)[None, :], jnp.argmax(bp, axis=2)]
        dest = jnp.where(bp.any(axis=2), first, fallback[:, None])
        thr_mat = jax.nn.one_hot(dest, J, dtype=f32)
        om = carry["omega"] * alive[:, None, :].astype(f32)
        rs = om.sum(axis=2, keepdims=True)
        om = jnp.where(rs > _EPS, om / jnp.maximum(rs, _EPS), thr_mat)
        use_om = (prm["plan_router"] > 0.5) & (carry["has_om"] > 0.5)
        Rm = jnp.where(use_om[:, :, None], om, thr_mat)

        # -- 4. route this bucket's arrivals (NIW parks under a QM) ----
        hq = prm["has_qm"]
        a_npo = jnp.stack([x["iw_n"], x["iw_p"], x["iw_o"]]) + \
            (1.0 - hq) * jnp.stack([x["niw_n"], x["niw_p"], x["niw_o"]])
        r_n, r_p, r_o = jnp.einsum("scj,cjk->sck", a_npo, Rm,
                                   precision=_HIGHEST)

        # -- 5. scaling policy ----------------------------------------
        cd_now = jnp.maximum(carry["cd"] - 1.0, 0.0)
        obs = x["obs"]
        mn = prm["min_inst"]
        # reactive: per-request util trigger, only on live endpoints
        d_re = jnp.where(u > prm["up"], 1.0,
                         jnp.where((u < prm["down"]) & (total > mn + 0.5),
                                   -1.0, 0.0))
        d_re = jnp.where((r_n > _EPS) & alive, d_re, 0.0)
        # LT-I / LT-U / LT-UA against hourly targets (-1 = no target)
        tgtv = carry["tgt"]
        has_t = tgtv > -0.5
        target = jnp.maximum(tgtv, mn)
        jump = jnp.where(has_t & (jnp.abs(target - total) > 0.49),
                         target - total, 0.0)
        fcv = jnp.maximum(carry["fc"], 1e-9)
        hour_b = prm["hour_b"]
        pos = jnp.mod(b.astype(f32), hour_b)
        in_win = (prm["lt_ua"] > 0.5) & (pos >= hour_b - prm["ua_win_b"])
        up_a = (u > prm["up"]) & (total < target - 0.5)
        dn_a = (u < prm["down"]) & (total > jnp.maximum(target, mn) + 0.5)
        ua_up = in_win & (total > target - 0.5) & \
            (obs >= prm["ua_hi"] * fcv) & (u > prm["up"])
        ua_dn = in_win & (total < target + 0.5) & (total > mn + 0.5) & \
            (obs <= prm["ua_lo"] * fcv)
        d_ltu = jnp.where(up_a, 1.0,
                          jnp.where(dn_a, -1.0,
                                    jnp.where(ua_up, 1.0,
                                              jnp.where(ua_dn, -1.0, 0.0))))
        d_ltu = jnp.where(has_t, d_ltu, 0.0)
        lt_i = prm["lt_i"] > 0.5
        d_lt = jnp.where(lt_i, jump, d_ltu)
        # chiron: offline-profile backpressure + NIW backlog drain.
        # The event loop's backlog signal sees NIW parked since the
        # previous tick, so the current bucket's inflow counts too.
        park_tok = _mm(PM, carry["park_p"] + carry["park_o"]
                       + hq * (x["niw_p"] + x["niw_o"])).sum(axis=1)
        bk_c = park_tok[jnp.asarray(st.cell_model)] / float(J)
        prof = prm["chiron_prof"][:, None]
        req_i = jnp.ceil(obs / jnp.maximum(prm["chiron_theta"] * prof,
                                           1e-9))
        req_b = jnp.ceil(bk_c[:, None] / jnp.maximum(prof * 3600.0, 1e-9))
        tgt_ch = jnp.maximum(req_i + req_b + prm["chiron_mixed"], mn)
        d_ch = jnp.where(jnp.abs(tgt_ch - total) > 0.49,
                         tgt_ch - total, 0.0)
        mode = prm["mode"]
        delta = jnp.where(mode == MODE_REACTIVE, d_re,
                          jnp.where(mode == MODE_LT, d_lt, d_ch))
        act = ((cd_now < 0.5) | lt_i) & (jnp.abs(delta) > 0.49)
        delta = jnp.where(act, delta, 0.0)
        cd = jnp.where(act & ~lt_i, prm["cd_b"], cd_now)

        # -- 6. actuate: spot acquisition (warm-first) and drains ------
        ok_dep = (dep_c > 0.5) & (down[None, :] < 0.5)
        want_up = jnp.where(ok_dep, jnp.maximum(delta, 0.0), 0.0)
        req_j = want_up.sum(axis=0)
        used_j = (live + pend + draining).sum(axis=0)
        avail_j = jnp.maximum(
            jnp.minimum(spot, jnp.maximum(prm["caps"] - used_j, 0.0)), 0.0)
        fac = jnp.where(req_j > _EPS,
                        jnp.minimum(1.0, avail_j / jnp.maximum(req_j,
                                                               _EPS)), 0.0)
        grant = want_up * fac[None, :]
        g_m = _mm(PM, grant)
        ratio = grant / jnp.maximum(_mm(PMT, g_m), _EPS)
        warm_take = jnp.minimum(grant, _mm(PMT, warm) * ratio)
        cold = grant - warm_take
        warm = jnp.maximum(warm - _mm(PM, warm_take), 0.0)
        spot = spot - grant.sum(axis=0)
        wloc_c = _mm(PMT, carry["wloc"])
        cold_loc = cold * jnp.where(wloc_c > 0.5, 1.0, 0.0)
        cold_rem = cold - cold_loc
        rows3 = jnp.concatenate([jnp.mod(b + SWAP, L),
                                 jnp.mod(b + LOCAL, L),
                                 jnp.mod(b + REMOTE, L)])
        ring = ring.at[rows3, CI3].add(
            jnp.concatenate([warm_take, cold_loc, cold_rem]))
        wloc = jnp.maximum(carry["wloc"],
                           jnp.where(_mm(PM, cold) > _EPS, 1.0, 0.0))
        want_dn = jnp.minimum(jnp.maximum(-delta, 0.0), live)
        live_after = live - want_dn
        drainq = drainq.at[jnp.mod(b + LD - 1, LD)].add(want_dn)

        # -- 7. queue manager: park NIW, forced + capacity releases ----
        park_p = carry["park_p"] + hq * x["niw_p"]
        park_o = carry["park_o"] + hq * x["niw_o"]
        park_n = carry["park_n"] + hq * x["niw_n"]
        pk_tot = park_n.sum(axis=1)
        need = jnp.clip(x["fcum"] - carry["relcum"], 0.0, pk_tot)
        fr = (need / jnp.maximum(pk_tot, _EPS))[:, None]
        rel_n, rel_p, rel_o = park_n * fr, park_p * fr, park_o * fr
        park_n, park_p, park_o = (park_n - rel_n, park_p - rel_p,
                                  park_o - rel_o)
        q_add_n, q_add_p, q_add_o = jnp.einsum(
            "scj,cjk->sck", jnp.stack([rel_n, rel_p, rel_o]), Rm,
            precision=_HIGHEST)
        relcum = carry["relcum"] + need
        per_inst = jnp.where(u < prm["qm_two"], 2.0,
                             jnp.where(u < prm["qm_one"], 1.0, 0.0))
        cap_dest = hq * jnp.where((u < prm["qm_sig"]) & (live_after > 0.5),
                                  per_inst * live_after, 0.0)
        cap_tot = cap_dest.sum(axis=1)
        pk_tot2 = park_n.sum(axis=1)
        take = jnp.minimum(cap_tot, pk_tot2)
        sf = (take / jnp.maximum(pk_tot2, _EPS))[:, None]
        rel2_p, rel2_o = park_p * sf, park_o * sf
        park_n, park_p, park_o = (park_n - park_n * sf, park_p - rel2_p,
                                  park_o - rel2_o)
        df = cap_dest / jnp.maximum(cap_tot[:, None], _EPS)
        q_add_n = q_add_n + take[:, None] * df
        q_add_p = q_add_p + rel2_p.sum(axis=1, keepdims=True) * df
        q_add_o = q_add_o + rel2_o.sum(axis=1, keepdims=True) * df
        relcum = relcum + take

        # -- 8/9. enqueue, admit to service, decode --------------------
        qn = carry["qn"] + r_n + q_add_n
        qp = carry["qp"] + r_p + q_add_p
        qo = carry["qo"] + r_o + q_add_o
        svc = live + draining
        pre_cap = PTPS * svc * dt
        slots = jnp.maximum(MB * svc - carry["d_n"], 0.0)
        frac = jnp.clip(jnp.minimum(pre_cap / jnp.maximum(qp, _EPS),
                                    slots / jnp.maximum(qn, _EPS)),
                        0.0, 1.0)
        adm_n, adm_p, adm_o = qn * frac, qp * frac, qo * frac
        qn, qp, qo = qn - adm_n, qp - adm_p, qo - adm_o
        f_tok = carry["f_tok"] + adm_p + adm_o
        d_n = carry["d_n"] + adm_n
        d_o = carry["d_o"] + adm_o
        occ = jnp.clip(d_n / jnp.maximum(MB * svc, _EPS), 0.0, 1.0)
        tbt = TBT0 * (1.0 + ALPHA * occ)
        srv_o = jnp.minimum(d_o, jnp.where(svc > _EPS,
                                           (d_n / tbt) * dt, 0.0))
        done_n = jnp.where(d_o > _EPS,
                           d_n * srv_o / jnp.maximum(d_o, _EPS), 0.0)
        rel_tok = jnp.where(d_n > _EPS,
                            f_tok * done_n / jnp.maximum(d_n, _EPS), f_tok)
        d_o, d_n, f_tok = d_o - srv_o, d_n - done_n, f_tok - rel_tok
        tiny = d_n < 1e-6
        d_o = jnp.where(tiny, 0.0, d_o)
        f_tok = jnp.where(tiny, 0.0, f_tok)
        d_n = jnp.where(tiny, 0.0, d_n)

        # -- 10. dead cells: drop queues past the retry budget ---------
        dead = jnp.where(live_after.sum(axis=1) < 0.5,
                         carry["dead"] + 1.0, 0.0)
        flush = (dead > prm["drop_budget_b"])[:, None]
        drop = jnp.where(flush, qn, 0.0)
        qn = jnp.where(flush, 0.0, qn)
        qp = jnp.where(flush, 0.0, qp)
        qo = jnp.where(flush, 0.0, qo)

        # -- 11. emissions for per-request reconstruction --------------
        delay_dest = jnp.where(
            qn >= 1.0,
            jnp.clip(qp * dt / jnp.maximum(adm_p + 0.5 * rel_tok, _EPS),
                     0.0, 1e6), 0.0)
        delay_h, tbt_h = jnp.einsum("cjk,sck->scj", Rm,
                                    jnp.stack([delay_dest, tbt]),
                                    precision=_HIGHEST)
        pk_fin = park_n.sum(axis=1)
        nw = jnp.where(hq > 0.5,
                       jnp.clip(0.5 * dt + pk_fin * dt /
                                jnp.maximum(take + need, _EPS),
                                0.5 * dt, prm["qm_age"]), 0.0)
        out = {"live": live_after, "f_tok": f_tok, "qp": qp, "qo": qo,
               "qn": qn, "d_o": d_o, "d_n": d_n, "ring": ring,
               "drainq": drainq, "spot": spot, "warm": warm,
               "wloc": wloc, "cd": cd, "tgt": carry["tgt"],
               "fc": carry["fc"], "dep": carry["dep"], "down": down,
               "dead": dead, "park_p": park_p, "park_o": park_o,
               "park_n": park_n, "relcum": relcum,
               "omega": carry["omega"], "has_om": carry["has_om"]}
        ys = {"delay": delay_h, "tbt": tbt_h, "nw": nw, "util": u,
              "inst": live + pend + draining, "waste": pend,
              "spot": spot, "done": done_n, "drop": drop,
              "so": grant.sum(), "si": want_dn.sum()}
        return out, ys

    return step


_SEG_CACHE: Dict[Tuple, Tuple] = {}
_SEG_CACHE_STATS = {"hits": 0, "misses": 0}


def seg_cache_stats() -> Dict[str, int]:
    """Uniform cache telemetry (see docs/PERF.md): lifetime hit/miss
    counts for the compiled-segment cache.  Unbounded, so evictions is
    always 0 — present for accessor uniformity with SolveCache and the
    forecast fit cache."""
    return {"hits": _SEG_CACHE_STATS["hits"],
            "misses": _SEG_CACHE_STATS["misses"],
            "evictions": 0, "entries": len(_SEG_CACHE)}


def _compiled_segments(st: _Static):
    """(single, batched) jit'd segment runners for this static config,
    cached process-wide so repeat runs and sweep batches sharing a
    group key pay the trace + compile cost once."""
    key = st.key()
    hit = _SEG_CACHE.get(key)
    if hit is not None:
        _SEG_CACHE_STATS["hits"] += 1
        return hit
    _SEG_CACHE_STATS["misses"] += 1
    step = _build_step(st)

    def run_seg(prm, carry, xs):
        return jax.lax.scan(lambda c, x: step(prm, c, x), carry, xs)

    # donated carry: the scan consumes the previous segment's state
    # in place (R6 checks this under src/repro/sim/vector)
    seg_single = jax.jit(run_seg, donate_argnums=(1,))  # reprolint: disable=R6 -- cache-once: stored in module-level _SEG_CACHE keyed by static config
    seg_batched = jax.jit(  # reprolint: disable=R6 -- cache-once: stored in module-level _SEG_CACHE keyed by static config
        jax.vmap(run_seg, in_axes=(0, 0, None)), donate_argnums=(1,))
    _SEG_CACHE[key] = (seg_single, seg_batched)
    return _SEG_CACHE[key]


def _init_carry(st: _Static, rp: ReplicaParams) -> Dict[str, np.ndarray]:
    C, J, M = st.C, st.J, st.M
    z = lambda *s: np.zeros(s, np.float32)
    dep_m = rp.dep0[::st.P].astype(np.float32)
    return {"live": rp.live0.astype(np.float32), "f_tok": z(C, J),
            "qp": z(C, J), "qo": z(C, J), "qn": z(C, J),
            "d_o": z(C, J), "d_n": z(C, J),
            "ring": z(st.L, C, J), "drainq": z(st.LD, C, J),
            "spot": np.full(J, rp.spot_spare, np.float32),
            "warm": z(M, J), "wloc": dep_m.copy(), "cd": z(C, J),
            "tgt": np.full((C, J), -1.0, np.float32), "fc": z(C, J),
            "dep": dep_m, "down": z(J), "dead": z(C),
            "park_p": z(C, J), "park_o": z(C, J), "park_n": z(C, J),
            "relcum": z(C),
            "omega": z(C, J, J), "has_om": z(C, J)}


def _prm(st: _Static, rp: ReplicaParams) -> Dict[str, np.ndarray]:
    dt = st.dt
    s = lambda v: np.float32(v)
    caps = np.where(np.isinf(rp.region_caps), 1e9,
                    rp.region_caps).astype(np.float32)
    return {"mode": np.int32(rp.mode),
            "lt_i": s(1.0 if (rp.mode == MODE_LT and
                              rp.lt_variant == LT_I) else 0.0),
            "lt_ua": s(1.0 if (rp.mode == MODE_LT and
                               rp.lt_variant == LT_UA) else 0.0),
            "up": s(rp.up), "down": s(rp.down),
            "cd_b": s(max(round(rp.cooldown_s / dt), 1)),
            "min_inst": s(rp.min_inst),
            "ua_hi": s(rp.ua_hi), "ua_lo": s(rp.ua_lo),
            "ua_win_b": s(rp.ua_window_s / dt),
            "hour_b": s(max(rp.hour_s / dt, 1.0)),
            "route_thr": s(rp.route_thr),
            "plan_router": s(1.0 if rp.plan_router else 0.0),
            "has_qm": s(1.0 if rp.has_qm else 0.0),
            "qm_sig": s(rp.qm_sig), "qm_one": s(rp.qm_one),
            "qm_two": s(rp.qm_two), "qm_age": s(rp.qm_promote_age),
            "chiron_theta": s(rp.chiron_theta),
            "chiron_mixed": s(rp.chiron_mixed),
            "chiron_prof": rp.chiron_prof.astype(np.float32),
            "drop_budget_b": s(rp.drop_budget_s / dt),
            "caps": caps}


class VectorBatch:
    """Run one *group* of replicas (same models/regions/pools/profiles/
    tick — see ``params.group_key``) in lockstep over one trace.

    ``batched=True`` steps all replicas under ``jax.vmap``;
    ``batched=False`` runs them sequentially through the unbatched jit
    (the parity baseline for the batch-of-1 test)."""

    def __init__(self, trace: Union[Trace, Sequence[Request]],
                 cfgs: Sequence[SimConfig],
                 names: Optional[Sequence[str]] = None,
                 models: Optional[List[str]] = None,
                 regions: Optional[List[str]] = None,
                 profiles: Optional[Dict[str, PerfProfile]] = None,
                 batched: bool = True,
                 control_workers: Optional[int] = None):
        if not isinstance(trace, Trace):
            trace = Trace.from_requests(trace)
        self.trace = trace.sorted_by_arrival()
        self.models = models or list(self.trace.models)
        self.regions = regions or list(self.trace.regions)
        self.profiles = profiles or {m: PROFILES[m] for m in self.models}
        names = names or [f"sim{i}" for i in range(len(cfgs))]
        self.rps = [extract(cfg, self.models, self.regions,
                            self.profiles, name)
                    for cfg, name in zip(cfgs, names)]
        keys = {group_key(rp, tuple(self.models), tuple(self.regions),
                          self.profiles) for rp in self.rps}
        if len(keys) > 1:
            raise VectorUnsupported(
                "replicas in one VectorBatch must share a group key "
                "(models/regions/pools/profiles/tick); got "
                f"{len(keys)} distinct keys")
        cfg0 = self.rps[0].cfg
        if cfg0.siloed and any(rp.mode != MODE_REACTIVE
                               for rp in self.rps):
            raise VectorUnsupported(
                "siloed pools with a non-reactive scaler have no "
                "vector lowering (LT/Chiron act on the unified pool)")
        self.batched = batched
        # plan solves run on a small thread pool (scipy/HiGHS releases
        # the GIL); results are collected in replica order, so the
        # emitted plans are identical for any worker count
        if control_workers is None:
            control_workers = int(os.environ.get(
                "REPRO_CONTROL_WORKERS",
                max(1, min(8, os.cpu_count() or 1))))
        self.control_workers = max(1, control_workers)
        #: per-boundary control-plane timing/dedupe totals, filled by
        #: ``run()`` — see docs/PERF.md "control plane at sweep scale"
        self.control_stats: Dict[str, float] = {}
        self.st = _Static(self.models, self.regions, self.rps[0].pools,
                          self.profiles, cfg0.tick)
        # segment-cache activity happens here (construction), so run()
        # reports deltas against this snapshot
        self._seg_stats0 = seg_cache_stats()
        self._seg_single, self._seg_batched = _compiled_segments(self.st)

    # ------------------------------------------------------------ plumbing
    def _expand(self, arr_mj: np.ndarray, pool: int) -> np.ndarray:
        """[B, M, J] model flow -> [B, C, J] with mass in one pool."""
        st = self.st
        B = arr_mj.shape[0]
        out = np.zeros((B, st.C, st.J), np.float32)
        for mi in range(st.M):
            out[:, mi * st.P + pool, :] = arr_mj[:, mi, :]
        return out

    def _build_xs(self, bk: BucketedTrace) -> Dict[str, np.ndarray]:
        st = self.st
        iw, niw = 0, st.niw_pool
        xs = {"iw_n": self._expand(bk.iw_n, iw),
              "iw_p": self._expand(bk.iw_p, iw),
              "iw_o": self._expand(bk.iw_o, iw),
              "niw_n": self._expand(bk.niw_n, niw),
              "niw_p": self._expand(bk.niw_p, niw),
              "niw_o": self._expand(bk.niw_o, niw)}
        obs = np.zeros((bk.n_buckets, st.C, st.J), np.float32)
        for mi in range(st.M):
            for p in range(st.P):
                obs[:, mi * st.P + p, :] = bk.obs_tps[:, mi, :]
        xs["obs"] = obs
        fcum = np.zeros((bk.n_buckets, st.C), np.float32)
        rp0 = self.rps[0]
        if rp0.has_qm:
            fm = bk.force_release_cum(rp0.qm_promote_age, rp0.qm_slack)
            for mi in range(st.M):
                fcum[:, mi * st.P + niw] = fm[:, mi]
        xs["fcum"] = fcum
        xs["b"] = np.arange(bk.n_buckets, dtype=np.int32)
        return xs

    # ------------------------------------------------------------ boundaries
    def _schedule(self, horizon: float) -> List[Tuple[int, int, str, int,
                                                      object]]:
        """Initial boundary heap: (bucket, seq, kind, replica, payload)."""
        dt = self.st.dt
        ev: List[Tuple[int, int, str, int, object]] = []
        seq = 0
        if any(rp.controller is not None for rp in self.rps):
            t = 3600.0
            while t < horizon:
                ev.append((int(round(t / dt)), seq, "hour", -1, None))
                seq += 1
                t += 3600.0
        for i, rp in enumerate(self.rps):
            sc = rp.scenario
            for o in (getattr(sc, "outages", ()) or ()):
                if o.region not in self.regions:
                    continue
                j = self.regions.index(o.region)
                ev.append((int(round(o.start / dt)), seq, "down", i, j))
                seq += 1
                ev.append((int(round(o.end / dt)), seq, "up", i, j))
                seq += 1
        heapq.heapify(ev)
        self._seq = seq
        return ev

    def _instances(self, cv: Dict[str, np.ndarray]
                   ) -> Dict[Tuple[str, str], int]:
        st = self.st
        live, ring = cv["live"], cv["ring"]
        pend = ring.sum(axis=0)
        instances: Dict[Tuple[str, str], int] = {}
        for mi, m in enumerate(st.models):
            for ji, r in enumerate(st.regions):
                n = sum(live[mi * st.P + p, ji] + pend[mi * st.P + p, ji]
                        for p in range(st.P))
                instances[(m, r)] = int(round(n))
        return instances

    def _feed_placement(self, rep_i: int,
                        cv: Dict[str, np.ndarray]) -> None:
        st, rp = self.st, self.rps[rep_i]
        feed = capability(rp.controller, "set_placement_state")
        if feed is None:
            return
        placed = frozenset((m, r) for mi, m in enumerate(st.models)
                           for ji, r in enumerate(st.regions)
                           if cv["dep"][mi, ji] > 0.5)
        wl = frozenset((m, r) for mi, m in enumerate(st.models)
                       for ji, r in enumerate(st.regions)
                       if cv["wloc"][mi, ji] > 0.5)
        ws = {(m, r): int(cv["warm"][mi, ji])
              for mi, m in enumerate(st.models)
              for ji, r in enumerate(st.regions)
              if cv["warm"][mi, ji] >= 1.0}
        dn = frozenset(r for ji, r in enumerate(st.regions)
                       if cv["down"][ji] > 0.5)
        feed(PlacementState(placed=placed, weights_local=wl,
                            warm_spot=ws, down_regions=dn))

    def _lookback(self, rep_i: int) -> float:
        cfg = self.rps[rep_i].cfg
        return max(cfg.history_lookback, 3600.0 + 2 * cfg.tps_window)

    def _apply_hour(self, rep_i: int, cv: Dict[str, np.ndarray],
                    t: float, bk: BucketedTrace,
                    heap: List) -> None:
        """Serial reference path: one replica's full hourly round —
        signal extraction, its own forecast, solve, apply."""
        rp = self.rps[rep_i]
        if rp.controller is None:
            return
        instances = self._instances(cv)
        self._feed_placement(rep_i, cv)
        plan = rp.controller.plan(t, instances,
                                  bk.planner_series(t, self._lookback(rep_i)),
                                  bk.niw_last_hour(t))
        self._apply_plan(rep_i, cv, t, plan, heap)

    def _apply_plan(self, rep_i: int, cv: Dict[str, np.ndarray],
                    t: float, plan, heap: List) -> None:
        """Write one replica's hourly plan into array state: stage or
        actuate placement actions, overwrite targets/forecasts/ω."""
        st, rp = self.st, self.rps[rep_i]
        if isinstance(plan, tuple):
            targets, forecasts = plan
            plan = Plan(t=t, targets=targets, forecasts=forecasts)
        # a NaN/inf written into the carry would poison every later
        # bucket of the scan without an error: refuse it here
        bad = sorted(k for d in (plan.targets, plan.forecasts)
                     for k, v in d.items() if not math.isfinite(v))
        if bad:
            raise ValueError(f"plan at t={t} has non-finite targets or "
                             f"forecasts for {bad}")
        if plan.placement is not None:
            for a in plan.placement.actions:
                bkt = int(round(a.effective_at / st.dt))
                if a.effective_at <= t:
                    self._apply_place(rep_i, cv, a, int(round(t / st.dt)))
                else:
                    heapq.heappush(heap, (bkt, self._seq, "place",
                                          rep_i, a))
                    self._seq += 1
        cv["tgt"][:] = -1.0
        cv["fc"][:] = 0.0
        for (m, r), v in plan.targets.items():
            if m in st.models and r in st.regions:
                mi, ji = st.models.index(m), st.regions.index(r)
                cv["tgt"][mi * st.P, ji] = float(v)
                cv["fc"][mi * st.P, ji] = float(
                    plan.forecasts.get((m, r), 0.0))
        cv["omega"][:] = 0.0
        cv["has_om"][:] = 0.0
        if rp.plan_router and plan.routing is not None:
            for (m, h), fr in plan.routing.fractions.items():
                if m not in st.models or h not in st.regions:
                    continue
                mi, hj = st.models.index(m), st.regions.index(h)
                row = np.asarray([max(fr.get(r, 0.0), 0.0)
                                  for r in st.regions])
                tot = row.sum()
                if tot <= 0.0:
                    continue
                for p in range(st.P):
                    cv["omega"][mi * st.P + p, hj, :] = row / tot
                    cv["has_om"][mi * st.P + p, hj] = 1.0

    def _apply_down(self, rep_i: int, cv: Dict[str, np.ndarray],
                    j: int) -> None:
        st = self.st
        cv["down"][j] = 1.0
        freed = cv["live"][:, j].copy()
        cv["live"][:, j] = 0.0
        pend = cv["ring"][:, :, j].sum(axis=0)
        drn = cv["drainq"][:, :, j].sum(axis=0)
        cv["spot"][j] += freed.sum() + pend.sum() + drn.sum()
        cv["warm"][:, j] += st.pm @ (freed + pend + drn)
        cv["ring"][:, :, j] = 0.0
        cv["drainq"][:, :, j] = 0.0
        # queued + in-flight work re-routes to the most-alive region
        for c in range(st.C):
            others = [k for k in range(st.J) if k != j]
            k = max(others, key=lambda kk: cv["live"][c, kk])
            cv["qn"][c, k] += cv["qn"][c, j] + cv["d_n"][c, j]
            cv["qp"][c, k] += cv["qp"][c, j]
            cv["qo"][c, k] += cv["qo"][c, j] + cv["d_o"][c, j]
            cv["f_tok"][c, k] += cv["f_tok"][c, j]
        for key in ("qn", "qp", "qo", "d_n", "d_o", "f_tok"):
            cv[key][:, j] = 0.0

    def _apply_place(self, rep_i: int, cv: Dict[str, np.ndarray],
                     act, b0: int) -> None:
        st = self.st
        if act.model not in st.models or act.region not in st.regions:
            return
        mi, ji = st.models.index(act.model), st.regions.index(act.region)
        if act.deploy:
            cv["dep"][mi, ji] = 1.0
            cv["wloc"][mi, ji] = 1.0
            return
        cv["dep"][mi, ji] = 0.0
        for p in range(st.P):
            c = mi * st.P + p
            n = cv["live"][c, ji]
            cv["live"][c, ji] = 0.0
            cv["drainq"][(b0 + st.LD - 1) % st.LD, c, ji] += n
            self._extra_si[rep_i] += n
            pend = cv["ring"][:, c, ji].sum()
            cv["spot"][ji] += pend
            cv["warm"][mi, ji] += pend
            cv["ring"][:, c, ji] = 0.0

    # --------------------------------------------------- batched boundaries
    def _hour_round_batched(self, carry, t: float, bk: BucketedTrace,
                            heap: List):
        """One hourly boundary for the whole batch: ``device_get`` only
        the aggregate-signal slices the planners read, run ONE
        fleet-wide stacked forecast, solve the per-replica ILPs on a
        thread pool (plans collected in replica order — identical for
        any worker count), then write the four plan keys back.  The
        rest of the carry stays device-resident.  Returns the updated
        carry (fully host-materialized only if a plan actuates a
        placement *now*, which touches far more than the plan slice)."""
        cs = self.control_stats
        ctrl = [i for i, rp in enumerate(self.rps)
                if rp.controller is not None]
        if not ctrl:
            return carry
        cs["boundaries"] += 1
        t0 = time.perf_counter()
        # np.array: device_get on CPU returns zero-copy read-only views
        # into device buffers the next (donating) segment call frees —
        # the boundary needs its own writable host copies
        pulled = {k: np.array(v) for k, v in jax.device_get(
            {k: carry[k] for k in _HOUR_READS + _HOUR_WRITES}).items()}
        cs["transfer_s"] += time.perf_counter() - t0
        cvs = {i: {k: pulled[k][i] for k in pulled} for i in ctrl}
        insts = {}
        for i in ctrl:
            self._feed_placement(i, cvs[i])
            insts[i] = self._instances(cvs[i])
        # histories come from the shared bucketized trace (host side)
        # and are identical across replicas with equal lookbacks:
        # build each distinct dict once
        t0 = time.perf_counter()
        hist_by_lb: Dict[float, Dict] = {}
        hists = {}
        for i in ctrl:
            lb = self._lookback(i)
            if lb not in hist_by_lb:
                hist_by_lb[lb] = bk.planner_series(t, lb)
            hists[i] = hist_by_lb[lb]
        niw = bk.niw_last_hour(t)
        fitted = self._fleet.fit({str(i): hists[i] for i in ctrl
                                  if self._fleet.batched(str(i))})
        cs["forecast_s"] += time.perf_counter() - t0

        def solve_one(i):
            rp = self.rps[i]
            fit = fitted.get(str(i))
            if fit is not None:
                fn = capability(rp.controller, "plan_fitted")
                return fn(t, insts[i], hists[i], niw, fit)
            return rp.controller.plan(t, insts[i], hists[i], niw)

        t0 = time.perf_counter()
        if self._pool is not None and len(ctrl) > 1:
            plans = list(self._pool.map(solve_one, ctrl))
        else:
            plans = [solve_one(i) for i in ctrl]
        cs["ilp_s"] += time.perf_counter() - t0
        cs["plans"] += len(plans)

        t0 = time.perf_counter()
        immediate = any(
            getattr(p, "placement", None) is not None and
            any(a.effective_at <= t for a in p.placement.actions)
            for p in plans)
        if immediate:
            carry = jax.tree_util.tree_map(
                np.array, jax.device_get(carry))
            for i, plan in zip(ctrl, plans):
                cv = {k: v[i] for k, v in carry.items()}
                self._apply_plan(i, cv, t, plan, heap)
        else:
            for i, plan in zip(ctrl, plans):
                self._apply_plan(i, cvs[i], t, plan, heap)
            carry = dict(carry)
            for k in _HOUR_WRITES:   # mutated through the cvs views
                carry[k] = pulled[k]
        cs["apply_s"] += time.perf_counter() - t0
        return carry

    # ------------------------------------------------------------ main loop
    def run(self) -> List[Report]:
        st = self.st
        cfg0 = self.rps[0].cfg
        tr = self.trace
        last_arrival = float(tr.arrival[-1]) if len(tr) else 0.0
        horizon = last_arrival + cfg0.drain_grace
        kv_caps = {m: self.profiles[m].kv_capacity_tokens
                   for m in st.models}
        bk = bucketize(tr, st.dt, horizon, kv_caps,
                       hist_window=cfg0.tps_window)
        xs_full = self._build_xs(bk)
        B = bk.n_buckets
        R = len(self.rps)
        self._extra_si = [0.0] * R
        accs = [ReplicaAccumulator(rp, st, bk) for rp in self.rps]
        heap = self._schedule(horizon)
        prms = [_prm(st, rp) for rp in self.rps]
        carries = [_init_carry(st, rp) for rp in self.rps]
        host = lambda tree: jax.tree_util.tree_map(
            np.array, jax.device_get(tree))
        self.control_stats = {"boundaries": 0, "plans": 0,
                              "forecast_s": 0.0, "ilp_s": 0.0,
                              "transfer_s": 0.0, "apply_s": 0.0}
        ctrl_ids = [i for i, rp in enumerate(self.rps)
                    if rp.controller is not None]
        self._fleet = FleetForecast(
            {str(i): self.rps[i].controller for i in ctrl_ids}) \
            if (self.batched and ctrl_ids) else None
        self._pool = None
        if (self.batched and self.control_workers > 1
                and len(ctrl_ids) > 1):
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.control_workers)
        sc0 = _SOLVE_CACHE.cache_stats()
        fc0 = fit_cache_stats()
        if self.batched:
            prm = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *prms)
            carry = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *carries)
        try:
            b0 = 0
            while b0 < B:
                events = []
                while heap and heap[0][0] <= b0:
                    events.append(heapq.heappop(heap))
                if events:
                    t = b0 * st.dt
                    if self.batched and all(
                            e[2] == "hour" and e[3] < 0 for e in events):
                        for _ in events:
                            carry = self._hour_round_batched(
                                carry, t, bk, heap)
                    else:
                        # mixed or per-replica events (outage down/up,
                        # staged placements): materialize and use the
                        # serial per-event path
                        if self.batched:
                            carry = host(carry)
                        for _, _, kind, ri, payload in events:
                            for i in (range(R) if ri < 0 else (ri,)):
                                cv = ({k: v[i] for k, v in carry.items()}
                                      if self.batched else carries[i])
                                if kind == "hour":
                                    self._apply_hour(i, cv, t, bk, heap)
                                elif kind == "down":
                                    self._apply_down(i, cv, payload)
                                elif kind == "up":
                                    cv["down"][payload] = 0.0
                                elif kind == "place":
                                    self._apply_place(i, cv, payload, b0)
                b1 = min(heap[0][0] if heap else B, B)
                b1 = max(b1, b0 + 1)
                xs_seg = {k: v[b0:b1] for k, v in xs_full.items()}
                if self.batched:
                    out, ys = self._seg_batched(prm, carry, xs_seg)
                    # host(): accumulators retain slices of ys past this
                    # segment, and zero-copy device_get views would alias
                    # buffers the next donating call reuses
                    ys = host(ys)
                    for i, acc in enumerate(accs):
                        acc.ingest(b0, {k: v[i] for k, v in ys.items()})
                    # the carry stays on device between segments; only
                    # boundary slices are ever transferred
                    carry = out
                else:
                    new_carries = []
                    for i, acc in enumerate(accs):
                        out, ys = self._seg_single(prms[i], carries[i],
                                                   xs_seg)
                        new_carries.append(host(out))
                        acc.ingest(b0, jax.device_get(ys))
                    carries = new_carries
                b0 = b1
        finally:
            if self._pool is not None:
                self._pool.shutdown(wait=False)
                self._pool = None
        if self._fleet is not None:
            for k, v in self._fleet.stats().items():
                self.control_stats[f"fleet_{k}"] = v
        # cache-fragmentation telemetry (T3's dynamic twin): per-run
        # deltas of every control-plane cache, aggregated by
        # benchmarks/run.py --week into BENCH_sim.json["control_week"]
        sc1 = _SOLVE_CACHE.cache_stats()
        fc1 = fit_cache_stats()
        sg1, sg0 = seg_cache_stats(), self._seg_stats0
        for k in ("hits", "misses", "evictions"):
            self.control_stats[f"ilp_cache_{k}"] = sc1[k] - sc0[k]
            self.control_stats[f"fit_cache_{k}"] = fc1[k] - fc0[k]
        self.control_stats["seg_cache_hits"] = sg1["hits"] - sg0["hits"]
        self.control_stats["seg_cache_misses"] = \
            sg1["misses"] - sg0["misses"]
        if self.batched:
            carry = host(carry)
        reports = []
        for i, acc in enumerate(accs):
            cv = ({k: v[i] for k, v in carry.items()}
                  if self.batched else carries[i])
            reports.append(acc.finalize(cv, self._extra_si[i]))
        return reports


class VectorSimulation:
    """Drop-in single-replica front end: same constructor shape as
    ``repro.sim.simulator.Simulation``, runs on the vector core."""

    def __init__(self, requests: Union[Trace, Sequence[Request]],
                 cfg: SimConfig, models: Optional[List[str]] = None,
                 regions: Optional[List[str]] = None,
                 profiles: Optional[Dict[str, PerfProfile]] = None,
                 name: str = "sim"):
        self._batch = VectorBatch(requests, [cfg], names=[name],
                                  models=models, regions=regions,
                                  profiles=profiles, batched=False)

    def run(self) -> Report:
        return self._batch.run()[0]
