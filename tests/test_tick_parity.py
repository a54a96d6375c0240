"""The device-resident tick (``scheduler_tick``, ``SynergAI(device=True)``)
against the numpy oracle at fleet scale (J~2048, W=256), covering the
padding edges (J not divisible by the 128-row job block, all-infeasible
rows, doomed jobs): one tick of the device policy places exactly what the
cached numpy policy places.

The kernel's gates — depth penalty, prefill/decode phase slicing, the
TTFT/TPOT streaming deadlines — and its placement walk are additionally
checked against a numpy transcription on messy inputs, and the float32
boundary-tie caveat is pinned as an executable contract.  ``run_tick``
and ``tick_oracle`` are shared with ``tests/test_kernels.py``."""

import numpy as np
import pytest

from repro.core.estimator import estimate_matrix
from repro.core.hierarchy import HierarchicalSynergAI
from repro.core.job import Job
from repro.core.scheduler import SynergAI
from repro.core.simulator import Simulator
from repro.core.workers import synth_fleet
from repro.core.workload import (MMPPArrivals, ParetoSize, TenantSpec,
                                 make_workload, scenario)


# ----------------------------------------------------------------------------
# shared helpers: one kernel call with its padding, and the numpy oracle


def tick_inputs(t, t_rem, *, pre=None, dec=None, pen=None, bw=None,
                avail=None, phase=None, has_ttft=None, has_tpot=None,
                ttft_rem=None, tpot_qos=None, dtok=None, emask=None,
                ekey=None):
    """One tick's inputs over J jobs x W workers, the neutral value
    (no phases, no deadlines, no penalty, every worker open) where an
    argument is left out."""
    J, W = t.shape
    return {
        "t": t, "pre": t if pre is None else pre,
        "dec": t if dec is None else dec, "t_rem": t_rem,
        "pen": np.ones(W) if pen is None else pen,
        "bw": np.zeros(W) if bw is None else bw,
        "avail": np.ones(W, bool) if avail is None else avail,
        "phase": np.zeros(J, np.int8) if phase is None else phase,
        "has_ttft": np.zeros(J, bool) if has_ttft is None else has_ttft,
        "has_tpot": np.zeros(J, bool) if has_tpot is None else has_tpot,
        "ttft_rem": np.full(J, np.inf) if ttft_rem is None else ttft_rem,
        "tpot_qos": np.full(J, np.inf) if tpot_qos is None else tpot_qos,
        "dtok": np.ones(J) if dtok is None else dtok,
        "emask": np.ones((1, W), bool) if emask is None else emask,
        "ekey": np.zeros(J, np.int32) if ekey is None else ekey,
    }


def run_tick(inp, bj=128, Jp=None, Wp=None):
    """``scheduler_tick`` (interpret mode) over ``inp``, the jobs padded
    to ``Jp`` rows (a multiple of ``bj``) and the workers to ``Wp``
    columns the way ``DeviceScoreCache.device_tick`` pads them; the row
    pools hold ``Jp`` rows, so a fixed (Jp, Wp) is one compile.  Returns
    (assign [J], order [J]); the padding rows must sort last, in order,
    and never be placed."""
    import jax.numpy as jnp

    from repro.kernels.scheduler_score import scheduler_tick

    J, W = inp["t"].shape
    Jp = Jp or -(-J // bj) * bj
    Wp = Wp or W

    def pool(a):
        out = np.full((Jp, Wp), np.inf, np.float32)
        out[:J, :W] = a
        return jnp.asarray(out)

    def padj(a, fill, dt):
        out = np.full(Jp, fill, dt)
        out[:J] = a
        return jnp.asarray(out)

    def padw(a, fill, dt):
        out = np.full(Wp, fill, dt)
        out[:W] = a
        return jnp.asarray(out)

    emask = np.zeros((len(inp["emask"]), Wp), bool)
    emask[:, :W] = inp["emask"]
    assign, order = scheduler_tick(
        pool(inp["t"]), pool(inp["pre"]), pool(inp["dec"]),
        jnp.zeros((1, Wp), jnp.float32),
        padj(np.arange(J), -1, np.int32),
        padj(inp["t_rem"], -1.0, np.float32),
        padj(inp["ttft_rem"], -1.0, np.float32),
        padj(inp["tpot_qos"], 1.0, np.float32),
        padj(inp["dtok"], 1.0, np.float32),
        padj(inp["has_ttft"], 0, np.int32),
        padj(inp["has_tpot"], 0, np.int32),
        padj(inp["phase"], 0, np.int32), padj(inp["ekey"], 0, np.int32),
        jnp.asarray(emask), padw(inp["pen"], 1.0, np.float32),
        padw(inp["bw"], 0.0, np.float32), padw(np.zeros(W), 0.0,
                                                np.float32),
        padw(inp["avail"], False, bool), bj=bj, interpret=True)
    assign, order = np.asarray(assign), np.asarray(order)
    np.testing.assert_array_equal(order[J:], np.arange(J, Jp))
    assert (assign[J:] == -1).all()
    return assign[:J], order[:J]


def tick_oracle(inp, dtype=np.float64):
    """Numpy transcription of the tick in ``dtype`` arithmetic: the
    phase-sliced, penalized Eq. 3 and streaming gates of
    ``SynergAI._schedule_cached``, the TTFT-tightened urgency, then the
    cost/eligibility prep and the greedy walk of ``SynergAI._place``."""
    f = lambda k: np.asarray(inp[k], dtype)
    t0, pre, dec, rem = f("t"), f("pre"), f("dec"), f("t_rem")
    pen, bw = f("pen"), f("bw")
    phase = np.asarray(inp["phase"])
    hft = np.asarray(inp["has_ttft"], bool)
    hpt = np.asarray(inp["has_tpot"], bool)
    t = np.where((phase == 1)[:, None], pre,
                 np.where((phase == 2)[:, None], dec, t0)) * pen
    acc = rem[:, None] >= t
    ttft_est = pre * pen
    tpot_est = dec * pen / f("dtok")[:, None]
    acc &= ((~hft | (phase == 2))[:, None]
            | (ttft_est <= f("ttft_rem")[:, None]))
    acc &= ((~hpt | (phase == 1))[:, None]
            | (tpot_est <= f("tpot_qos")[:, None]))
    with np.errstate(invalid="ignore"):
        urg = rem - t0.min(axis=1)
        slack = f("ttft_rem") - ttft_est.min(axis=1)
    urg = np.where(hft & (phase != 2), np.minimum(urg, slack), urg)
    doom = ~acc.any(axis=1)
    feas = np.isfinite(t)
    costd = t + bw
    best = np.where(feas, costd, np.inf).min(axis=1)
    elig = np.where(doom[:, None],
                    feas & (t <= dtype(1.5) * best[:, None]), acc)
    elig &= np.asarray(inp["emask"])[np.asarray(inp["ekey"])]
    ranked = np.where(elig, np.where(doom[:, None], costd, t), np.inf)
    order = np.lexsort((urg, doom))
    assign = np.full(len(rem), -1)
    open_slots = np.asarray(inp["avail"], bool).copy()
    for ji in order:
        cand = np.where(open_slots, ranked[ji], np.inf)
        wi = int(cand.argmin())
        if np.isfinite(cand[wi]):
            assign[ji] = wi
            open_slots[wi] = False
    return {"order": order, "assign": assign, "acc": acc, "doom": doom,
            "elig": elig, "urg": urg}


# ----------------------------------------------------------------------------
# fleet scale: one tick of the device policy vs the cached numpy policy


def _fleet_queue(cd, n_jobs):
    """A messy fleet-scale queue: bursty multi-tenant mix, heavy-tail
    sizes, a slice of doomed jobs (impossible QoS) and a slice of
    all-infeasible rows (engine unknown to the ConfigDict)."""
    tenants = [
        TenantSpec("mix", MMPPArrivals((0.5, 3.0), (120.0, 60.0)),
                   n_jobs - 64, sizes=ParetoSize()),
        # doomed: QoS far below anything any worker can deliver
        TenantSpec("doomed", MMPPArrivals((0.5, 3.0), (120.0, 60.0)), 32,
                   qos_scale=1e-3),
    ]
    jobs = make_workload(cd, tenants, seed=13)
    # all-infeasible rows: an engine no worker has a profile for
    for i in range(32):
        jobs.append(Job(len(jobs), "unknown-engine/bf16", 1000, 60.0,
                        float(i)))
    return jobs


@pytest.mark.parametrize("J", [2048, 2043])
def test_device_tick_matches_numpy_at_fleet_scale(configdict, J):
    fleet = synth_fleet(86, 85, 85)
    workers = [w.name for w in fleet]
    assert len(workers) == 256
    jobs = _fleet_queue(configdict, J)[:J]
    now = float(np.median([j.arrival for j in jobs]))  # t_rem straddles 0

    def tick(pol):
        cluster = Simulator(configdict, pol, fleet=fleet).cluster
        return [(a.job.id, a.worker, a.entry)
                for a in pol.schedule(now, jobs, cluster)]

    want = tick(SynergAI())
    assert tick(SynergAI(device=True)) == want
    assert len(want) == len(workers)        # every open worker filled
    # the queue really exercised the doomed and all-infeasible rows
    s = estimate_matrix(configdict, jobs, workers, now)
    assert (s.best_worker == -1).any()
    assert s.doomed.any() and not s.doomed.all()


# ----------------------------------------------------------------------------
# the tick's gates and walk vs the numpy transcription


def _gate_inputs(configdict, J, seed):
    """A messy tick input set: fleet-scale matrices with infeasible
    columns and all-infeasible rows, mixed phases, live depth penalties,
    streaming deadlines on a slice of the queue, busy waits, a mostly
    open fleet and per-key admission masks.  ``now`` is the last arrival,
    so open workers outnumber the jobs they accept and doomed jobs place
    too, by busy wait plus estimate."""
    from repro.core.estimator import phase_split_matrices, score_matrices

    rng = np.random.default_rng(seed)
    fleet = synth_fleet(86, 85, 85)
    workers = [w.name for w in fleet]
    W = len(workers)
    jobs = _fleet_queue(configdict, J)[:J]
    now = max(j.arrival for j in jobs)
    qps, pre = score_matrices(configdict, jobs, workers)
    q = np.array([float(j.queries) for j in jobs])
    with np.errstate(divide="ignore", invalid="ignore"):
        t0 = np.where(qps > 0, pre + q[:, None] / qps, np.inf)
    pre_m, dec_m = phase_split_matrices(configdict, jobs, workers)
    has_ttft = rng.random(J) < 0.4
    ttft_qos = np.where(has_ttft, rng.uniform(0.5, 50.0, J), np.inf)
    has_tpot = rng.random(J) < 0.4
    return tick_inputs(
        t0, np.array([j.t_qos - (now - j.arrival) for j in jobs]),
        pre=pre_m, dec=dec_m,
        pen=np.where(rng.random(W) < 0.5,
                     1.0 + 0.5 * rng.integers(1, 8, W), 1.0),
        bw=np.where(rng.random(W) < 0.5, rng.uniform(0.0, 30.0, W), 0.0),
        avail=rng.random(W) < 0.9,
        phase=rng.integers(0, 3, J).astype(np.int8),
        has_ttft=has_ttft, has_tpot=has_tpot,
        ttft_rem=ttft_qos - rng.uniform(0.0, 5.0, J),
        tpot_qos=np.where(has_tpot, rng.uniform(1e-5, 1e-2, J), np.inf),
        dtok=rng.integers(100, 200_000, J).astype(np.float64),
        emask=rng.random((3, W)) < 0.8,
        ekey=rng.integers(0, 3, J).astype(np.int32))


@pytest.mark.parametrize("J", [1024, 1021])
def test_tick_gates_match_numpy_oracle(configdict, J):
    inp = _gate_inputs(configdict, J, seed=17)
    assign, order = run_tick(inp)
    want = tick_oracle(inp, np.float32)
    np.testing.assert_array_equal(order, want["order"])
    np.testing.assert_array_equal(assign, want["assign"])
    # float32 scoring may flip a gate whose two sides tie to the last
    # few bits; every flip against the float64 oracle is such a tie
    o64 = tick_oracle(inp)
    flips = want["acc"] != o64["acc"]
    pen = inp["pen"][None, :]
    sides = (
        (np.broadcast_to(inp["t_rem"][:, None], flips.shape),
         np.where((inp["phase"] == 1)[:, None], inp["pre"],
                  np.where((inp["phase"] == 2)[:, None], inp["dec"],
                           inp["t"])) * pen),
        (np.broadcast_to(inp["ttft_rem"][:, None], flips.shape),
         inp["pre"] * pen),
        (np.broadcast_to(inp["tpot_qos"][:, None], flips.shape),
         inp["dec"] * pen / inp["dtok"][:, None]))
    with np.errstate(invalid="ignore"):
        tie = np.zeros_like(flips)
        for a, b in sides:
            tie |= np.abs(a - b) <= 1e-5 * np.maximum(np.abs(a), np.abs(b))
    assert not (flips & ~tie).any()
    assert flips.mean() < 0.001
    # the messy inputs really exercised the edges
    feas = np.isfinite(inp["t"])
    assert (~feas).any(axis=1).any() and (~feas).all(axis=1).any()
    assert want["doom"].any() and not want["doom"].all()
    assert (inp["pen"] != 1.0).any()                # live depth penalties
    assert set(np.unique(inp["phase"])) == {0, 1, 2}
    placed = assign >= 0
    assert (placed & want["doom"]).any() and (placed & ~want["doom"]).any()


# ----------------------------------------------------------------------------
# the float32 boundary-tie caveat, as an executable contract: a budget
# that ties its estimate at the last float64 bit may flip between
# acceptable and doomed (the kernel scores in f32), and that divergence
# is confined to the tie — everything with real margin agrees exactly


def _tie_inputs(gate, rem0):
    """2 jobs x 2 workers, only worker 0 open.  ``eq3``: job 0's budget
    ``rem0`` against its worker-0 estimate t = 0.25 + 100/2.0 = 50.25
    (exact in f32 and f64); ``ttft``: job 0's TTFT budget ``rem0``
    against its worker-0 prefill estimate 10.25, with an ample Eq. 1
    budget.  Job 1 has generous margins everywhere, so whichever job
    sorts first takes worker 0 and the flip shows in the placement."""
    t = np.array([[50.25, 100.5], [50.25, 25.5]])
    avail = np.array([True, False])
    if gate == "eq3":
        return tick_inputs(t, np.array([rem0, 60.0]), avail=avail)
    return tick_inputs(t, np.array([200.0, 200.0]), avail=avail,
                       pre=np.array([[10.25, 20.5], [10.25, 20.5]]),
                       has_ttft=np.array([True, False]),
                       ttft_rem=np.array([rem0, np.inf]))


@pytest.mark.parametrize("gate,edge", [("eq3", 50.25), ("ttft", 10.25)])
def test_f32_boundary_tie_contract_tick(gate, edge):
    # one f64 ulp below the estimate: float64 rejects, while the f32
    # cast lands exactly on the estimate and accepts
    rem0 = np.nextafter(edge, 0.0)
    assert np.float32(rem0) == np.float32(edge)
    inp = _tie_inputs(gate, rem0)
    o64 = tick_oracle(inp)
    assert not o64["acc"][0].any() and o64["doom"][0]   # doomed in f64
    assert o64["acc"][1].all()
    assign, order = run_tick(inp, bj=8)
    o32 = tick_oracle(inp, np.float32)
    np.testing.assert_array_equal(order, o32["order"])
    np.testing.assert_array_equal(assign, o32["assign"])
    # divergence confined to the tie cell, which un-dooms job 0: it now
    # sorts first and takes the one open worker
    diff = o32["acc"] != o64["acc"]
    assert diff.sum() == 1 and diff[0, 0]
    assert list(order) == [0, 1] and list(o64["order"]) == [1, 0]
    assert list(assign) == [0, -1] and list(o64["assign"]) == [-1, 0]


def test_f32_off_boundary_exact_parity():
    """One ulp of *f32* margin is already enough: nudge the budget a
    float32 step off the estimate in either direction and the kernel
    agrees with the float64 oracle everywhere."""
    for rem0 in (np.float64(np.nextafter(np.float32(50.25),
                                         np.float32(0.0))),
                 np.float64(np.nextafter(np.float32(50.25),
                                         np.float32(100.0)))):
        inp = _tie_inputs("eq3", rem0)
        o64 = tick_oracle(inp)
        np.testing.assert_array_equal(tick_oracle(inp, np.float32)["acc"],
                                      o64["acc"])
        assign, order = run_tick(inp, bj=8)
        np.testing.assert_array_equal(order, o64["order"])
        np.testing.assert_array_equal(assign, o64["assign"])


# ----------------------------------------------------------------------------
# zero-job ticks


def test_zero_job_score_result_shared_shape(configdict):
    from repro.core.estimator import ScoreResult
    workers = [w.name for w in synth_fleet(1, 2, 2)]
    empty = ScoreResult.empty(workers)
    assert empty.workers == workers
    assert empty.t_estimated.shape == (0, len(workers))
    assert empty.acceptable.shape == (0, len(workers))
    for arr in (empty.t_remaining, empty.best_worker, empty.urgency,
                empty.doomed):
        assert arr.shape == (0,)
    # the numpy estimator returns the same shaped empty (a hand-built
    # variant used to drift)
    got = estimate_matrix(configdict, [], workers, now=0.0)
    assert got.workers == workers
    assert got.t_estimated.shape == (0, len(workers))
    assert got.best_worker.shape == (0,)


@pytest.mark.parametrize("variant", ["numpy", "uncached", "device", "hier",
                                     "hier-device"])
def test_zero_job_tick_all_variants(configdict, variant):
    pol = {
        "numpy": lambda: SynergAI(),
        "uncached": lambda: SynergAI(incremental=False),
        "device": lambda: SynergAI(device=True),
        "hier": lambda: HierarchicalSynergAI(),
        "hier-device": lambda: HierarchicalSynergAI(device=True),
    }[variant]()
    fleet = synth_fleet(1, 2, 2, regions=2 if "hier" in variant else 0)
    cluster = Simulator(configdict, pol, fleet=fleet).cluster
    assert pol.schedule(0.0, [], cluster) == []
    # and with a queue that empties: the next tick stays well-formed
    jobs = scenario(configdict, "poisson", n_jobs=4, fleet=fleet,
                    seed=2)
    out = pol.schedule(0.0, list(jobs), cluster)
    assert out                      # something placed on idle workers
    assert pol.schedule(1.0, [], cluster) == []
