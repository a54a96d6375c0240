"""SynergAI scoring on the Pallas kernels — drop-in ``score_fn``s.

``make_pallas_score_fn()`` builds the dense ``[J, W]`` qps/preproc
matrices from the Configuration Dictionary (cached rows shared with the
numpy estimator via ``score_matrices``), runs
``repro.kernels.scheduler_score`` — compiled on a TPU backend, interpret
mode on any other (``interpret=None``, the default) — and adapts the outputs to ``ScoreResult`` so that

    SynergAI(score_fn=make_pallas_score_fn())

is a drop-in replacement for the default numpy path.

``make_pallas_score_fn(v2=True)`` returns the *fused* backend instead:
``repro.kernels.scheduler_score.scheduler_score_v2`` folds the batched
queue-depth penalty, the prefill/decode phase slicing of disaggregated
pools, and the TTFT/TPOT streaming gates into the same kernel pass, so

    SynergAI(score_fn=make_pallas_score_fn(v2=True))

covers ``serving="batched"`` + streaming scoring on-accelerator with no
numpy post-processing.  The fused callable carries ``fused = True`` and
is invoked by ``SynergAI`` with the cached solo matrices
(``repro.core.scorecache``) plus the per-tick cluster vectors — see
``SynergAI._schedule_fused`` for the exact contract.

Parity (identical assignments at fleet scale, padding edges included) is
enforced by ``tests/test_pallas_parity.py`` over profiled catalogues.
One caveat: the kernels score in float32, so a job whose remaining QoS
budget ties its estimated time to the last float64 bit can flip between
acceptable and doomed relative to the numpy scorer — real profiles keep
orders of magnitude more margin than that, but exact boundary ties are
not part of the guarantee.
"""

from __future__ import annotations

import numpy as np

from repro.core.estimator import ScoreResult, score_matrices
from repro.kernels.ops import resolve_interpret


def make_pallas_score_fn(bj: int = 128, interpret=None,
                         v2: bool = False, device_cache: bool = False):
    interpret = resolve_interpret(interpret)
    if device_cache:
        return _make_device_marker(bj, interpret)
    if v2:
        return _make_fused_score_fn(bj, interpret)
    from repro.kernels.scheduler_score import scheduler_score

    def score_fn(cd, jobs, workers, now, use_default=False,
                 token=None) -> ScoreResult:
        if not jobs:
            return ScoreResult.empty(workers)
        t_rem = np.array([j.t_qos - (now - j.arrival) for j in jobs])
        qps, pre = score_matrices(cd, jobs, workers, use_default, token)
        q = np.array([float(j.queries) for j in jobs], np.float32)
        est, best, urg, acc = scheduler_score(
            qps.astype(np.float32), pre.astype(np.float32), q,
            t_rem.astype(np.float32), bj=bj, interpret=interpret)
        # BIG-sentinel entries (qps <= 0) become inf so candidate_order's
        # feasibility filter behaves exactly like the numpy path
        t_est = np.where(qps > 0, np.asarray(est, np.float64), np.inf)
        acceptable = np.asarray(acc).astype(bool)
        return ScoreResult(list(workers), t_est, t_rem, acceptable,
                           np.asarray(best, np.int64),
                           np.asarray(urg, np.float64),
                           ~acceptable.any(axis=1))

    score_fn.takes_token = True
    return score_fn


def _make_device_marker(bj: int, interpret: bool):
    """``make_pallas_score_fn(device_cache=True)`` — the device-resident
    backend.  Unlike the other variants this is a *marker*, not a scoring
    callable: ``SynergAI`` consumes its attributes to build a
    ``repro.core.devicecache.DeviceScoreCache`` (persistent on-device row
    pools) and routes every tick through the fused ``scheduler_tick``
    kernel dispatch, so no host-side score function ever runs.
    ``interpret`` arrives resolved; ``SynergAI`` hands it to the cache,
    where a chip run checks it (``pol.cache.interpret is False``)."""
    def device_score(*_a, **_k):
        raise TypeError(
            "make_pallas_score_fn(device_cache=True) returns a backend "
            "marker consumed by SynergAI, not a callable score_fn — the "
            "tick runs through DeviceScoreCache.device_tick")
    device_score.device_cache = True
    device_score.takes_profile = True
    device_score.bj = bj
    device_score.interpret = interpret
    return device_score


def _make_fused_score_fn(bj: int, interpret: bool):
    from repro.kernels.scheduler_score import scheduler_score_v2

    def fused_score(t_solo, pre_m, dec_m, t_rem, pen, phase, has_ttft,
                    has_tpot, ttft_rem, tpot_qos, dtok):
        """(t_eff, acceptable, urgency, doomed) — the fused batched +
        streaming + disaggregated scoring pass, as float64/bool numpy
        (``inf`` marks infeasible pairs, exactly like the numpy path)."""
        f32 = lambda a: np.asarray(a, np.float32)
        est, acc, urg, doom = scheduler_score_v2(
            f32(t_solo), f32(pre_m), f32(dec_m), f32(t_rem), f32(pen),
            np.asarray(phase, np.int32), np.asarray(has_ttft, np.int32),
            np.asarray(has_tpot, np.int32), f32(ttft_rem), f32(tpot_qos),
            f32(dtok), bj=bj, interpret=interpret)
        return (np.asarray(est, np.float64),
                np.asarray(acc).astype(bool),
                np.asarray(urg, np.float64),
                np.asarray(doom).astype(bool))

    fused_score.fused = True
    return fused_score
