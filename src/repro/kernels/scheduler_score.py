"""SynergAI Eq. 2-4 scoring Pallas TPU kernels (v1 + fused v2).

At fleet scale (thousands of queued jobs x hundreds of worker pools) the
scheduler's scoring step is itself a dense [J, W] compute:

    T_est[j, w]   = preproc[j, w] + q[j] / qps[j, w]          (Eq. 2)
    acceptable    = T_rem[j] >= T_est[j, w]                   (Eq. 3)
    best[j]       = argmin_w T_est[j, w] over acceptable      (Eq. 4)
    urgency[j]    = T_rem[j] - min_w T_est[j, w]

Grid walks J-blocks with the full worker axis resident in VMEM; infeasible
(j, w) pairs carry qps <= 0 and are excluded via masking.  Validated against
``repro.core.estimator.estimate_matrix`` (the numpy oracle) in the tests.

``scheduler_score_v2`` fuses the whole batched-serving scoring pipeline —
the queue-depth penalty (``1 + alpha * b`` per worker), the
prefill/decode phase slicing of disaggregated pools, and the TTFT/TPOT
streaming-deadline gates — into a single pass, so the Pallas path covers
``serving="batched"`` + streaming scoring end-to-end instead of falling
back to numpy post-processing.  It consumes the *precomputed* solo
matrices (full service, prefill prefix, decode remainder, ``inf`` marking
infeasible pairs — exactly what ``repro.core.scorecache`` persists across
ticks) and emits the effective times, the gated acceptability, the
TTFT-tightened urgency, and doom.  The numpy oracle is the batched +
streaming block of ``repro.core.scheduler.SynergAI``; parity, including
the padding edges, is pinned in ``tests/test_pallas_parity.py``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 3.0e38


def _score_kernel(qps_ref, pre_ref, q_ref, rem_ref,
                  est_ref, best_ref, urg_ref, acc_ref):
    qps = qps_ref[...]                  # [BJ, W]
    pre = pre_ref[...]
    q = q_ref[...]                      # [BJ, 1]
    rem = rem_ref[...]                  # [BJ, 1]

    feas = qps > 0.0
    est = jnp.where(feas, pre + q / jnp.where(feas, qps, 1.0), BIG)
    acc = feas & (rem >= est)
    est_masked = jnp.where(acc, est, BIG)
    # argmin over acceptable; fall back to argmin over feasible
    any_acc = jnp.any(acc, axis=1, keepdims=True)
    pick_from = jnp.where(any_acc, est_masked, est)
    best = jnp.argmin(pick_from, axis=1)
    any_feas = jnp.any(feas, axis=1)
    best = jnp.where(any_feas, best, -1)
    urgency = rem[:, 0] - jnp.min(est, axis=1)

    est_ref[...] = est
    best_ref[...] = best.astype(jnp.int32)
    urg_ref[...] = urgency
    acc_ref[...] = acc.astype(jnp.int8)


def scheduler_score(qps, preproc, queries, t_remaining, *, bj=128,
                    interpret=False):
    """qps, preproc: [J, W] f32 (qps <= 0 marks infeasible); queries,
    t_remaining: [J] f32.  Returns (t_est [J,W], best [J], urgency [J],
    acceptable [J,W] int8)."""
    J, W = qps.shape
    bj = min(bj, J)
    pad = (-J) % bj
    if pad:
        z = lambda a, fill: jnp.pad(a, [(0, pad)] + [(0, 0)] *
                                    (a.ndim - 1), constant_values=fill)
        qps, preproc = z(qps, 0.0), z(preproc, 0.0)
        queries, t_remaining = z(queries, 1.0), z(t_remaining, -1.0)
        J = J + pad
    out = pl.pallas_call(
        _score_kernel,
        grid=(J // bj,),
        in_specs=[
            pl.BlockSpec((bj, W), lambda i: (i, 0)),
            pl.BlockSpec((bj, W), lambda i: (i, 0)),
            pl.BlockSpec((bj, 1), lambda i: (i, 0)),
            pl.BlockSpec((bj, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bj, W), lambda i: (i, 0)),
            pl.BlockSpec((bj,), lambda i: (i,)),
            pl.BlockSpec((bj,), lambda i: (i,)),
            pl.BlockSpec((bj, W), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((J, W), jnp.float32),
            jax.ShapeDtypeStruct((J,), jnp.int32),
            jax.ShapeDtypeStruct((J,), jnp.float32),
            jax.ShapeDtypeStruct((J, W), jnp.int8),
        ],
        interpret=interpret,
    )(qps.astype(jnp.float32), preproc.astype(jnp.float32),
      queries.astype(jnp.float32)[:, None],
      t_remaining.astype(jnp.float32)[:, None])
    est, best, urg, acc = out
    n = J - pad
    return est[:n], best[:n], urg[:n], acc[:n]


def _score_v2_kernel(t_ref, pre_ref, dec_ref, rem_ref, pen_ref, phase_ref,
                     hft_ref, hpt_ref, trem_ref, tq_ref, dtok_ref,
                     est_ref, acc_ref, urg_ref, doom_ref):
    t = t_ref[...]                      # [BJ, W] solo full service (inf=infeasible)
    pre = pre_ref[...]                  # [BJ, W] prefill prefix
    dec = dec_ref[...]                  # [BJ, W] decode remainder
    rem = rem_ref[...]                  # [BJ, 1] Eq. 1 remaining budget
    pen = pen_ref[...]                  # [1, W] queue-depth penalty
    phase = phase_ref[...]              # [BJ, 1] 0 full / 1 prefill / 2 decode
    has_ttft = hft_ref[...] != 0        # [BJ, 1]
    has_tpot = hpt_ref[...] != 0
    ttft_rem = trem_ref[...]            # [BJ, 1] TTFT budget minus waiting
    tpot_qos = tq_ref[...]              # [BJ, 1] (inf = no deadline)
    dtok = dtok_ref[...]                # [BJ, 1] decoded tokens (inf = n/a)

    # phase-sliced, depth-penalized effective service time
    t_eff = jnp.where(phase == 1, pre, jnp.where(phase == 2, dec, t))
    t_eff = t_eff * pen
    acc = rem >= t_eff                                        # Eq. 3
    # streaming gates on the penalized phase split: a decode-phase job's
    # TTFT is history, a prefill-phase job's TPOT belongs to its decode
    ttft_est = pre * pen
    tpot_est = dec * pen / dtok
    acc &= (~has_ttft) | (phase == 2) | (ttft_est <= ttft_rem)
    acc &= (~has_tpot) | (phase == 1) | (tpot_est <= tpot_qos)
    # urgency decays from the *solo* estimate (matching the numpy path);
    # a scarce TTFT budget can become the binding urgency
    urg = rem[:, 0] - jnp.min(t, axis=1)
    ttft_slack = ttft_rem[:, 0] - jnp.min(ttft_est, axis=1)
    urg = jnp.where(has_ttft[:, 0] & (phase[:, 0] != 2),
                    jnp.minimum(urg, ttft_slack), urg)
    doom = ~jnp.any(acc, axis=1)

    est_ref[...] = t_eff
    acc_ref[...] = acc.astype(jnp.int8)
    urg_ref[...] = urg
    doom_ref[...] = doom.astype(jnp.int8)


def scheduler_score_v2(t_solo, prefill, decode, t_remaining, pen, phase,
                       has_ttft, has_tpot, ttft_rem, tpot_qos, dtok, *,
                       bj=128, interpret=False):
    """Fused batched + streaming + disaggregated scoring.

    t_solo, prefill, decode: [J, W] f32 solo-service matrices (``inf``
    marks infeasible pairs); pen: [W] f32 depth penalty; t_remaining,
    ttft_rem, tpot_qos, dtok: [J] f32; phase: [J] int (0/1/2); has_ttft,
    has_tpot: [J] int (0/1).  Returns (t_eff [J,W], acceptable [J,W]
    int8, urgency [J], doomed [J] int8)."""
    J, W = t_solo.shape
    bj = min(bj, J)
    pad = (-J) % bj
    if pad:
        z = lambda a, fill: jnp.pad(a, [(0, pad)] + [(0, 0)] *
                                    (a.ndim - 1), constant_values=fill)
        inf = jnp.inf
        t_solo, prefill, decode = (z(t_solo, inf), z(prefill, inf),
                                   z(decode, inf))
        t_remaining, ttft_rem = z(t_remaining, -1.0), z(ttft_rem, -1.0)
        tpot_qos, dtok = z(tpot_qos, 1.0), z(dtok, 1.0)
        phase, has_ttft, has_tpot = (z(phase, 0), z(has_ttft, 0),
                                     z(has_tpot, 0))
        J = J + pad
    col = lambda a, dt: a.astype(dt)[:, None]
    jw = pl.BlockSpec((bj, W), lambda i: (i, 0))
    j1 = pl.BlockSpec((bj, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _score_v2_kernel,
        grid=(J // bj,),
        in_specs=[jw, jw, jw, j1,
                  pl.BlockSpec((1, W), lambda i: (0, 0)),   # pen: resident
                  j1, j1, j1, j1, j1, j1],
        out_specs=[jw,
                   jw,
                   pl.BlockSpec((bj,), lambda i: (i,)),
                   pl.BlockSpec((bj,), lambda i: (i,))],
        out_shape=[
            jax.ShapeDtypeStruct((J, W), jnp.float32),
            jax.ShapeDtypeStruct((J, W), jnp.int8),
            jax.ShapeDtypeStruct((J,), jnp.float32),
            jax.ShapeDtypeStruct((J,), jnp.int8),
        ],
        interpret=interpret,
    )(t_solo.astype(jnp.float32), prefill.astype(jnp.float32),
      decode.astype(jnp.float32), col(t_remaining, jnp.float32),
      pen.astype(jnp.float32)[None, :], col(phase, jnp.int32),
      col(has_ttft, jnp.int32), col(has_tpot, jnp.int32),
      col(ttft_rem, jnp.float32), col(tpot_qos, jnp.float32),
      col(dtok, jnp.float32))
    est, acc, urg, doom = out
    n = J - pad
    return est[:n], acc[:n], urg[:n], doom[:n]


# ---------------------------------------------------------------------------
# fused whole-tick kernel: scoring + placement in one device dispatch
#
# ``scheduler_tick`` is the device-resident path's entry point
# (``repro.core.devicecache.DeviceScoreCache``): the Eq. 2 row pools stay
# on-device across ticks, so a steady-state tick ships only O(churn * W)
# row bytes plus O(J + W) per-tick vectors, gathers the live rows by slot
# index on-device, runs the fused scoring kernel below, and finishes the
# whole Eq. 4 placement (urgency-ordered greedy masked argmin over open
# slots) inside the same jit dispatch — the host gets back just the
# (job, worker) assignment indices.


def _tick_kernel(t_ref, pre_ref, dec_ref, rem_ref, pen_ref, bw_ref,
                 phase_ref, hft_ref, hpt_ref, trem_ref, tq_ref, dtok_ref,
                 cost_ref, elig_ref, urg_ref, doom_ref):
    """The v2 scoring recipe extended through the placement-cost prep of
    ``SynergAI._place``: emits the ranking cost (doomed rows carry the
    busy-wait completion cost), the eligibility mask (doomed rows use the
    1.5x-of-best gate over feasible workers, everything else the gated
    acceptability), the TTFT-tightened urgency and doom."""
    t = t_ref[...]                      # [BJ, W] solo full service (inf=infeasible)
    pre = pre_ref[...]                  # [BJ, W] prefill prefix
    dec = dec_ref[...]                  # [BJ, W] decode remainder
    rem = rem_ref[...]                  # [BJ, 1] Eq. 1 remaining budget
    pen = pen_ref[...]                  # [1, W] queue-depth penalty
    bw = bw_ref[...]                    # [1, W] busy/failed wait
    phase = phase_ref[...]              # [BJ, 1] 0 full / 1 prefill / 2 decode
    has_ttft = hft_ref[...] != 0        # [BJ, 1]
    has_tpot = hpt_ref[...] != 0
    ttft_rem = trem_ref[...]            # [BJ, 1] TTFT budget minus waiting
    tpot_qos = tq_ref[...]              # [BJ, 1] (inf = no deadline)
    dtok = dtok_ref[...]                # [BJ, 1] decoded tokens (inf = n/a)

    t_eff = jnp.where(phase == 1, pre, jnp.where(phase == 2, dec, t))
    t_eff = t_eff * pen
    acc = rem >= t_eff                                        # Eq. 3
    ttft_est = pre * pen
    tpot_est = dec * pen / dtok
    acc &= (~has_ttft) | (phase == 2) | (ttft_est <= ttft_rem)
    acc &= (~has_tpot) | (phase == 1) | (tpot_est <= tpot_qos)
    urg = rem[:, 0] - jnp.min(t, axis=1)
    ttft_slack = ttft_rem[:, 0] - jnp.min(ttft_est, axis=1)
    urg = jnp.where(has_ttft[:, 0] & (phase[:, 0] != 2),
                    jnp.minimum(urg, ttft_slack), urg)
    doom_row = ~jnp.any(acc, axis=1, keepdims=True)           # [BJ, 1]
    # placement-cost prep (SynergAI._place): doomed jobs minimize
    # expected completion (wait + exec) within 1.5x of their best option;
    # everyone else walks their acceptable set by the effective time
    feas = jnp.isfinite(t_eff)
    costd = t_eff + bw
    best = jnp.min(jnp.where(feas, costd, jnp.inf), axis=1,
                   keepdims=True)
    eligd = feas & (t_eff <= 1.5 * best)
    cost_ref[...] = jnp.where(doom_row, costd, t_eff)
    # Mosaic selects and stores 32-bit lanes only: no select between
    # boolean vectors, no 8-bit outputs
    elig_ref[...] = jnp.where(doom_row, eligd.astype(jnp.int32),
                              acc.astype(jnp.int32))
    urg_ref[...] = urg[:, None]
    doom_ref[...] = doom_row.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("use_energy", "bj", "interpret"))
def scheduler_tick(pool_t, pool_pre, pool_dec, pool_ene, slots, t_rem,
                   ttft_rem, tpot_qos, dtok, has_ttft, has_tpot, phase,
                   ekey, emask, pen, busy_wait, escale, open0, *,
                   use_energy=False, bj=128, interpret=False):
    """One whole scheduling decision as a single device dispatch.

    pool_t/pool_pre/pool_dec/pool_ene: [cap, W] f32 device-resident row
    pools (padded columns/unwritten slots hold inf/garbage — both are
    masked out); slots: [Jp] i32 row indices (-1 = padding); t_rem,
    ttft_rem, tpot_qos, dtok: [Jp] f32; has_ttft, has_tpot, phase, ekey:
    [Jp] i32; emask: [K, W] bool batch-admission masks (``ekey`` indexes
    rows; all-true single row when serving is job-level); pen, busy_wait,
    escale: [W] f32; open0: [W] bool open (idle) workers.

    Jp must be a multiple of ``bj``.  Returns (assign [Jp] i32 — worker
    index or -1, order [Jp] i32 — the urgency-sorted placement order),
    bit-matching ``SynergAI._place`` over the same float32 inputs:
    stable (urgency, doomed) lexsort, then a greedy masked argmin per job
    with lowest-index tie-breaks, stopping once every open slot is
    filled."""
    Jp = slots.shape[0]
    cap, W = pool_t.shape
    if Jp % bj:
        raise ValueError(f"Jp={Jp} must be a multiple of bj={bj}")
    idx = jnp.clip(slots, 0, max(cap - 1, 0))
    t0 = pool_t[idx]
    pre_m = pool_pre[idx]
    dec_m = pool_dec[idx]
    col = lambda a, dt: a.astype(dt)[:, None]
    row = lambda a: a.astype(jnp.float32)[None, :]
    jw = pl.BlockSpec((bj, W), lambda i: (i, 0))
    j1 = pl.BlockSpec((bj, 1), lambda i: (i, 0))
    w1 = pl.BlockSpec((1, W), lambda i: (0, 0))
    # urgency and doom leave as [Jp, 1] columns: rank-1 blocks of bj
    # rows do not match the chip's tiling
    cost, elig, urg, doom = pl.pallas_call(
        _tick_kernel,
        grid=(Jp // bj,),
        in_specs=[jw, jw, jw, j1, w1, w1, j1, j1, j1, j1, j1, j1],
        out_specs=[jw, jw, j1, j1],
        out_shape=[
            jax.ShapeDtypeStruct((Jp, W), jnp.float32),
            jax.ShapeDtypeStruct((Jp, W), jnp.int32),
            jax.ShapeDtypeStruct((Jp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Jp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(t0, pre_m, dec_m, col(t_rem, jnp.float32), row(pen),
      row(busy_wait), col(phase, jnp.int32), col(has_ttft, jnp.int32),
      col(has_tpot, jnp.int32), col(ttft_rem, jnp.float32),
      col(tpot_qos, jnp.float32), col(dtok, jnp.float32))
    elig = elig != 0
    urg, doom = urg[:, 0], doom[:, 0]
    if use_energy:
        # the weighted energy/carbon term joins the *ranking* cost only;
        # eligible pairs always carry finite energy rows, so no masking
        cost = cost + pool_ene[idx] * escale[None, :]
    # batch-formation admission + padding masks
    jvalid = slots >= 0
    elig = elig & emask[ekey] & jvalid[:, None]
    ranked = jnp.where(elig, cost, jnp.inf)
    # 2D Ordered Job Queue: urgent first, doomed last, padding after
    # everything (stable sort keeps queue order on ties, like np.lexsort)
    doomkey = jnp.where(jvalid, doom, 2)
    urgkey = jnp.where(jvalid, urg, jnp.inf)
    order = jnp.lexsort((urgkey, doomkey))
    # greedy placement: walk jobs in order, each takes the masked argmin
    # over the still-open slots (argmin tie-breaks at the lowest worker
    # index, exactly like the numpy path's stable candidate walk)
    assign0 = jnp.full((Jp,), -1, jnp.int32)
    n_open0 = jnp.sum(open0.astype(jnp.int32))

    def body(i, carry):
        open_slots, assign, n_open = carry
        ji = order[i]
        cand = jnp.where(open_slots, ranked[ji], jnp.inf)
        wi = jnp.argmin(cand).astype(jnp.int32)
        ok = (n_open > 0) & jnp.isfinite(cand[wi])
        assign = assign.at[ji].set(jnp.where(ok, wi, assign[ji]))
        open_slots = open_slots.at[wi].set(open_slots[wi] & ~ok)
        return open_slots, assign, n_open - ok.astype(jnp.int32)

    _, assign, _ = jax.lax.fori_loop(0, Jp, body,
                                     (open0, assign0, n_open0))
    return assign, order.astype(jnp.int32)
