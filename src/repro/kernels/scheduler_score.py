"""SynergAI's scheduling decision (paper Eq. 1-4) as one Pallas TPU
dispatch: ``scheduler_tick``.

At fleet scale (thousands of queued jobs x hundreds of worker pools)
the decision is a dense [J, W] compute followed by a greedy walk:

    t_eff[j, w]  = phase-sliced Eq. 2 estimate x queue-depth penalty
    acceptable   = t_rem[j] >= t_eff[j, w], and the TTFT/TPOT gates  (Eq. 3)
    urgency[j]   = t_rem[j] - min_w t_solo[j, w], TTFT-tightened
    placement    = urgency-ordered masked argmin over open workers (Eq. 4)

The Eq. 2 rows stay on the device across ticks
(``repro.core.devicecache.DeviceScoreCache``), so a steady tick ships
only O(churn * W) row bytes plus O(J + W) per-tick vectors.
``scheduler_tick`` gathers the live rows by slot index, runs the scoring
kernel ``_tick_kernel`` over J-blocks with the worker axis resident in
VMEM, and finishes the placement inside the same jit dispatch; the host
gets back only the (job, worker) assignment indices.  The oracle is the
cached numpy path of ``repro.core.scheduler.SynergAI``; parity, padding
edges included, is pinned in ``tests/test_tick_parity.py``,
``tests/test_devicecache.py`` and ``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def resolve_interpret(interpret=None) -> bool:
    """``None`` -> interpret everywhere but on a TPU backend (the lowered
    Mosaic kernels there, the Pallas interpreter on any other, which is
    how the tests run them on CPU).  A caller that must not fall back
    checks the resolved value."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _tick_kernel(t_ref, pre_ref, dec_ref, rem_ref, pen_ref, bw_ref,
                 phase_ref, hft_ref, hpt_ref, trem_ref, tq_ref, dtok_ref,
                 cost_ref, elig_ref, urg_ref, doom_ref):
    """Eq. 2-4 scoring through the placement-cost prep of
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
