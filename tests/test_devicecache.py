"""Device-resident score cache: differential invalidation + fused-tick
parity + transfer accounting (``repro.core.devicecache``).

The acceptance anchors of the device-residency PR:

* a differential harness drives random interleavings of arrivals,
  placements, failures, elastic clones and profile refreshes through
  ``ScoreCache`` and ``DeviceScoreCache`` simultaneously and asserts
  row-for-row equality after every step — the device mirror inherits
  every invalidation rule the host cache established, with exactly one
  sanctioned divergence (a pure ``fail_gen`` bump *masks* on the device
  path instead of flushing, because failure state never enters the
  rows);
* ``SynergAI(device=True)`` is bit-for-bit the cached numpy scheduler
  in interpret mode — the PR 2/4 golden digests reproduce in both
  serving modes, flat and ``RegionView``-sliced hierarchical;
* steady-state host->device traffic is O(churn * W), not O(J * W).
"""

import dataclasses
import hashlib

import numpy as np
import pytest
from conftest import given, settings, st

from repro.core.devicecache import DeviceScoreCache
from repro.core.estimator import (new_profile_id, profile_overlay,
                                  profile_gen)
from repro.core.hierarchy import HierarchicalSynergAI
from repro.core.scheduler import SynergAI
from repro.core.scorecache import ScoreCache
from repro.core.simulator import Simulator
from repro.core.workers import synth_fleet
from repro.core.workload import scenario, synth_failures

from test_streaming_qos import PR2_GOLDEN, STREAM_GOLDEN
from test_trace_replay import REPLAY_GOLDEN_DIGEST

_APPROX = 1e-9


def _result_key(results):
    return [(r.job.id, r.worker, r.config, r.start, r.end, r.waiting,
             r.exec_s, r.e2e, r.violated, r.excess, r.ttft, r.tpot)
            for r in results]


# ---------------------------------------------------------------------------
# differential invalidation harness: host cache vs device mirror through
# random interleavings of every invalidation-relevant event


def _assert_mirrors_equal(hc, dc, cd, queue, cluster):
    """Sync both caches on the same state; every view must agree exactly
    (host mirrors are the same f64 computation) and every device row
    must be the f32 cast of its host row."""
    hs = hc.sync(cd, queue, cluster)
    ds = dc.sync(cd, queue, cluster)
    np.testing.assert_array_equal(hc.t_matrix(hs), dc.t_matrix(ds))
    np.testing.assert_array_equal(hc.min_estimate(hs),
                                  dc.min_estimate(ds))
    np.testing.assert_array_equal(hc.t_remaining(hs, 0.0),
                                  dc.t_remaining(ds, 0.0))
    if len(queue):
        W = dc._W
        pool = np.asarray(dc._dt)
        np.testing.assert_array_equal(
            pool[ds, :W], dc._t[ds].astype(np.float32))
        if dc._have_phase:
            pre, dec = dc.phase_matrices(ds)
            np.testing.assert_array_equal(
                np.asarray(dc._dpre)[ds, :W], pre.astype(np.float32))
            np.testing.assert_array_equal(
                np.asarray(dc._ddec)[ds, :W], dec.astype(np.float32))
        # padded device columns stay inf (self-masking in the kernel)
        assert np.isinf(pool[ds, W:]).all()


def _drive(configdict, ops, seed=13):
    """Apply an op sequence to one live cluster while a plain ScoreCache
    and a DeviceScoreCache track the same queue."""
    cd = configdict
    fleet = synth_fleet(1, 2, 2)
    sim = Simulator(cd, SynergAI(), fleet=fleet)
    cluster = sim.cluster
    pid = new_profile_id()
    hc = ScoreCache(profile=pid)
    dc = DeviceScoreCache(profile=pid)
    pool = list(scenario(cd, "poisson", n_jobs=160, fleet=fleet,
                         seed=seed))
    queue = [pool.pop(0) for _ in range(12)]
    engines = sorted({j.engine for j in pool})
    names = list(cluster.arrays.names)
    now, clones = 0.0, 0
    _assert_mirrors_equal(hc, dc, cd, queue, cluster)
    for step, op in enumerate(ops):
        now += 1.0
        if op == "arrive":
            queue.extend(pool.pop(0) for _ in range(min(3, len(pool))))
        elif op == "place":
            if queue:
                queue.pop(step % len(queue))
        elif op == "fail":
            cluster.workers[names[step % len(names)]].failed_until = \
                now + 5.0
        elif op == "clone":
            clones += 1
            base = cluster.workers["cloud-pod"].pool
            clone = dataclasses.replace(
                base, name=f"cloud-pod__clone{clones}")
            cluster.workers[clone.name] = cluster._make_worker(clone)
            names = list(cluster.arrays.names)
        elif op == "profile":
            profile_overlay(cd, pid).apply(
                {engines[step % len(engines)]:
                 {names[0]: 0.5 + 0.1 * (step % 4)}})
        _assert_mirrors_equal(hc, dc, cd, queue, cluster)
    # sanctioned divergence only: the device path converts pure fail_gen
    # flushes into masks, so it never flushes more often than the host
    assert dc.flushes <= hc.flushes
    assert dc.col_extends == hc.col_extends
    assert dc.profile_reclaims == hc.profile_reclaims
    return hc, dc


_OPS = ("arrive", "place", "fail", "clone", "profile")


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_differential_interleavings_seeded(configdict, seed):
    rng = np.random.default_rng(seed)
    ops = [
        _OPS[i] for i in rng.integers(0, len(_OPS), size=24)]
    _drive(configdict, ops, seed=13 + seed)


@given(ops=st.lists(st.sampled_from(_OPS), min_size=1, max_size=24))
@settings(max_examples=25, deadline=None)
def test_differential_interleavings_property(ops):
    from repro.core.offline import characterize
    _drive(characterize(), ops)


def test_fail_gen_masks_instead_of_flushing(configdict):
    """A pure failure-generation bump keeps every device row resident:
    the host rule flushes conservatively, the mirror masks — failure
    state never enters the Eq. 2 rows, so the kept rows are exactly what
    a recompute would produce (asserted row-for-row by the harness)."""
    cd = configdict
    fleet = synth_fleet(1, 2, 2)
    cluster = Simulator(cd, SynergAI(), fleet=fleet).cluster
    jobs = list(scenario(cd, "poisson", n_jobs=40, fleet=fleet, seed=5))
    dc = DeviceScoreCache()
    dc.sync(cd, jobs, cluster)
    rows0, up0 = dict(dc._slot), dc.rows_uploaded
    cluster.workers["edge-large"].failed_until = 50.0
    hc = ScoreCache()
    hc.sync(cd, jobs, cluster)      # fresh host cache, post-failure rows
    _assert_mirrors_equal(hc, dc, cd, jobs, cluster)
    assert dc.fail_masks == 1
    assert dc.flushes == 0
    assert dc._slot == rows0               # every slot survived
    assert dc.rows_uploaded == up0         # zero re-upload


def test_elastic_clone_extends_device_columns(configdict):
    """Appending a pool widens the device pools in place: the old block
    moves device-to-device and only the new columns upload."""
    cd = configdict
    fleet = synth_fleet(1, 2, 2)
    cluster = Simulator(cd, SynergAI(), fleet=fleet).cluster
    jobs = list(scenario(cd, "poisson", n_jobs=30, fleet=fleet, seed=9))
    dc = DeviceScoreCache()
    slots = dc.sync(cd, jobs, cluster)
    bytes0 = dc.bytes_to_device
    base = cluster.workers["cloud-pod"].pool
    clone = dataclasses.replace(base, name="cloud-pod__clone1")
    cluster.workers[clone.name] = cluster._make_worker(clone)
    slots = dc.sync(cd, jobs, cluster)
    assert dc.col_extends == 1 and dc.flushes == 0
    W = dc._W
    np.testing.assert_array_equal(
        np.asarray(dc._dt)[slots, :W], dc._t[slots].astype(np.float32))
    # one new column for the live rows, not a row re-upload
    assert dc.bytes_to_device - bytes0 < len(jobs) * 16 * 4
    # retiring the clone is a non-append membership change: full flush,
    # device pools drop and rebuild on the next sync
    del cluster.workers[clone.name]
    slots = dc.sync(cd, jobs, cluster)
    assert dc.flushes == 1
    np.testing.assert_array_equal(
        np.asarray(dc._dt)[slots, :dc._W],
        dc._t[slots].astype(np.float32))


def test_profile_refresh_reships_only_touched_rows(configdict):
    """A profile overlay refresh reclaims exactly the refreshed engine's
    slots (the PR 7 rule); only those rows travel back to the device."""
    cd = configdict
    fleet = synth_fleet(1, 2, 2)
    cluster = Simulator(cd, SynergAI(), fleet=fleet).cluster
    jobs = list(scenario(cd, "poisson", n_jobs=60, fleet=fleet, seed=6))
    pid = new_profile_id()
    dc = DeviceScoreCache(profile=pid)
    dc.sync(cd, jobs, cluster)
    up0 = dc.rows_uploaded
    target = sorted({j.engine for j in jobs})[0]
    profile_overlay(cd, pid).apply({target: {fleet[0].name: 0.5}})
    slots = dc.sync(cd, jobs, cluster)
    touched = sum(j.engine == target for j in jobs)
    assert dc.profile_reclaims == touched
    assert dc.rows_uploaded - up0 == touched
    np.testing.assert_array_equal(
        np.asarray(dc._dt)[slots, :dc._W],
        dc._t[slots].astype(np.float32))


# ---------------------------------------------------------------------------
# drop-in scheduling parity: device path == cached numpy path, bit-for-bit


def _drop_in_case(cd, case):
    """(jobs, Simulator keywords) of one drop-in scenario."""
    if case == "paper-DH-FH":
        from repro.core.job import make_experiment
        return make_experiment(cd, "DH", "FH", seed=11), dict(seed=11)
    if case == "fleet-disagg-streaming":
        fleet = synth_fleet(2, 3, 3, disaggregate=True)
        jobs = scenario(cd, "mmpp", n_jobs=120, fleet=fleet, seed=3,
                        utilization=1.0, serving="batched",
                        streaming=(2.0, 2.5))
        return jobs, dict(fleet=fleet, seed=3, serving="batched")
    serving, streaming, disaggregate = {
        "job-None-False": ("job", None, False),
        "batched-None-False": ("batched", None, False),
        "batched-streaming2-False": ("batched", (2.0, 2.5), False),
        "batched-streaming3-True": ("batched", (2.0, 2.5), True),
    }[case]
    fleet = synth_fleet(1, 2, 2, disaggregate=disaggregate)
    jobs = scenario(cd, "mmpp", n_jobs=60, fleet=fleet, seed=7,
                    utilization=1.2, serving=serving,
                    streaming=streaming)
    return jobs, dict(fleet=fleet, seed=7, serving=serving)


@pytest.mark.parametrize("case", ["job-None-False", "batched-None-False",
                                  "batched-streaming2-False",
                                  "batched-streaming3-True", "paper-DH-FH",
                                  "fleet-disagg-streaming"])
def test_device_drop_in_matches_numpy(configdict, case):
    """Byte-identical schedules, job for job: the four mixes on the
    5-pool fleet, the paper's DH-FH experiment on its 3-pool testbed,
    and a disaggregated batched streaming MMPP on 8 pools."""
    cd = configdict
    jobs, kw = _drop_in_case(cd, case)
    run = lambda pol: _result_key(Simulator(cd, pol, **kw).run(jobs))
    assert run(SynergAI(device=True)) == run(SynergAI())


@pytest.mark.parametrize("serving", ["job", "batched"])
def test_device_drop_in_under_failures_elastic_energy(configdict,
                                                      serving):
    cd = configdict
    fleet = synth_fleet(1, 2, 2)
    jobs = scenario(cd, "mmpp", n_jobs=120, fleet=fleet, seed=3,
                    utilization=1.2, serving=serving)
    span = jobs[-1].arrival
    kw = dict(fleet=fleet, seed=3, serving=serving,
              failures=synth_failures(fleet, span, mtbf_s=span / 2,
                                      mttr_s=span / 6, seed=5),
              elastic_max=3, elastic_threshold=4)
    run = lambda pol: _result_key(Simulator(cd, pol, **kw).run(jobs))
    assert run(SynergAI(device=True, energy_weight=0.5)) == \
        run(SynergAI(energy_weight=0.5))


def test_pr2_golden_reproduced_device(configdict):
    fleet = synth_fleet(1, 2, 2)
    jobs = scenario(configdict, "mmpp", n_jobs=40, fleet=fleet, seed=7,
                    utilization=1.2, serving="batched")
    res = {r.job.id: r for r in
           Simulator(configdict, SynergAI(device=True),
                     fleet=fleet, seed=7, serving="batched").run(jobs)}
    for jid, worker, start, end, exec_s, violated in PR2_GOLDEN:
        r = res[jid]
        assert r.worker == worker
        assert r.start == pytest.approx(start, rel=_APPROX)
        assert r.end == pytest.approx(end, rel=_APPROX)
        assert r.exec_s == pytest.approx(exec_s, rel=_APPROX)
        assert r.violated == violated


def test_stream_golden_reproduced_device(configdict):
    fleet = synth_fleet(1, 1, 1)
    jobs = scenario(configdict, "poisson", n_jobs=12, fleet=fleet,
                    seed=11, utilization=1.0, serving="batched")
    res = {r.job.id: r for r in
           Simulator(configdict, SynergAI(device=True),
                     fleet=fleet, seed=11, serving="batched").run(jobs)}
    for jid, ttft, tpot in STREAM_GOLDEN:
        assert res[jid].ttft == pytest.approx(ttft, rel=_APPROX), jid
        assert res[jid].tpot == pytest.approx(tpot, rel=_APPROX), jid


def test_replay_golden_digest_device_flat_and_hier(configdict,
                                                   tmp_path):
    from repro.core.workload import save_trace, replay
    jobs = scenario(configdict, "mmpp", n_jobs=40,
                    fleet=synth_fleet(1, 2, 2), seed=7, utilization=1.2)
    path = tmp_path / "golden.jsonl"
    save_trace(path, jobs)

    def digest(pol, fleet):
        res = Simulator(configdict, pol, fleet=fleet,
                        seed=7).run(replay(str(path)))
        canon = "\n".join(
            f"{r.job.id},{r.worker},{r.config},{r.start!r},{r.end!r},"
            f"{r.ttft!r},{r.tpot!r},{int(r.violated)}"
            for r in sorted(res, key=lambda r: r.job.id))
        return hashlib.sha256(canon.encode()).hexdigest()

    assert digest(SynergAI(device=True),
                  synth_fleet(1, 2, 2)) == REPLAY_GOLDEN_DIGEST
    assert digest(HierarchicalSynergAI(device=True),
                  synth_fleet(1, 2, 2, regions=1)) == \
        REPLAY_GOLDEN_DIGEST


@pytest.mark.parametrize("regions", [2, 3])
def test_hierarchical_region_sliced_device(configdict, regions):
    """Each region core carries its own DeviceScoreCache over the
    RegionView slice; the schedule matches the numpy hierarchy."""
    cd = configdict
    fleet = synth_fleet(1, 2, 2, regions=regions)
    jobs = scenario(cd, "mmpp", n_jobs=80, fleet=fleet, seed=5,
                    utilization=1.1, serving="batched")
    pol = HierarchicalSynergAI(device=True)
    got = _result_key(Simulator(cd, pol, fleet=fleet, seed=5,
                                serving="batched").run(jobs))
    want = _result_key(Simulator(cd, HierarchicalSynergAI(),
                                 fleet=fleet, seed=5,
                                 serving="batched").run(jobs))
    assert got == want
    assert pol._subs
    for sub in pol._subs.values():
        assert isinstance(sub.cache, DeviceScoreCache)
        assert sub.cache.rows_uploaded > 0


# ---------------------------------------------------------------------------
# transfer accounting: O(churn * W) per steady tick, not O(J * W)


def test_steady_tick_transfer_is_o_churn_w(configdict):
    cd = configdict
    fleet = synth_fleet(2, 4, 4)
    cluster = Simulator(cd, SynergAI(), fleet=fleet).cluster
    jobs = list(scenario(cd, "poisson", n_jobs=512, fleet=fleet,
                         seed=21))
    pol = SynergAI(device=True)
    queue = list(jobs[:480])
    spare = list(jobs[480:])
    pol.schedule(0.0, queue, cluster)    # cold tick: every row uploads
    dc = pol.cache
    assert dc.rows_uploaded == len(queue)
    full_matrix = len(queue) * dc._d_Wp * 4    # one [J, W] f32 re-upload
    # steady ticks: no arrivals -> zero matrix rows travel, only the
    # O(J + W) per-tick vectors
    b0, u0 = dc.bytes_to_device, dc.rows_uploaded
    for i in range(5):
        pol.schedule(1.0 + i, queue, cluster)
    assert dc.rows_uploaded == u0
    per_tick = (dc.bytes_to_device - b0) / 5
    assert per_tick < 0.25 * full_matrix
    # churn tick: exactly the arrivals' rows ship
    churn = 16
    queue.extend(spare[:churn])
    b1, u1 = dc.bytes_to_device, dc.rows_uploaded
    pol.schedule(10.0, queue, cluster)
    assert dc.rows_uploaded - u1 == churn
    assert dc.bytes_to_device - b1 < per_tick + 4 * churn * dc._d_Wp * 8
    assert dc.flushes == 0


def test_device_counters_over_full_run(configdict):
    """End-to-end: a 120-job run uploads each row once and never
    flushes; per-tick traffic stays far below a full-matrix ship."""
    cd = configdict
    fleet = synth_fleet(1, 2, 2)
    jobs = scenario(cd, "mmpp", n_jobs=120, fleet=fleet, seed=3,
                    utilization=1.2)
    pol = SynergAI(device=True)
    Simulator(cd, pol, fleet=fleet, seed=3).run(jobs)
    dc = pol.cache
    assert dc.flushes == 0
    assert dc.rows_uploaded == 120
    assert dc.ticks >= 100
    full_matrix_per_tick = 120 * dc._d_Wp * 4
    assert dc.bytes_to_device / dc.ticks < 0.5 * full_matrix_per_tick
