"""Fig. 7/8/9/10 analogues: the three experiments (DL-FL, DL-FH, DH-FH) for
all policies + SLO-MAEL comparison, aggregated over seeds — plus the
fleet-scale benches:

* ``bench_fleet``   — 10k-job x 64-pool MMPP scenario under every policy on
  the event-heap engine, and the old-vs-new simulator wall-clock
  head-to-head (seed tick-scanning loop vs indexed event heap).
* ``bench_serving`` — job-level vs batched (serving bridge) QoS and
  wall-clock on an overloaded MMPP fleet scenario: the same trace served
  with exclusive job occupancy vs continuous batching
  (``serving="batched"``).
* ``bench_streaming`` — streaming QoS under the bridge: the mmpp overload
  preset with per-class TTFT/TPOT deadlines, served on an aggregated
  fleet vs a prefill/decode-disaggregated one
  (``synth_fleet(..., disaggregate=...)``).  Headline: disaggregation
  cuts TTFT violations (prefill pools turn over fast; decodes can't camp
  on them) at the cost of TPOT pressure on the shrunken decode side.
* ``bench_sched_overhead`` — per-tick scheduler *decision* wall-clock
  under a standing MMPP backlog with queue churn: the PR4 uncached
  full-matrix path vs the incremental score-cache path
  (``SynergAI(incremental=False)`` vs default) vs the device-resident
  tick (``SynergAI(device=True)``), sweeping J up to 50k jobs and W up
  to 256 pools.  Writes ``BENCH_SCHED.json`` (``--sched-json``) — the
  committed copy at the repo root is the perf-trajectory baseline that
  ``tools/check_perf_regression.py`` gates nightly CI against.
* ``bench_traces`` — the trace-driven scenario subsystem: every policy on
  (a) a *replayed* mmpp overload trace (exported with ``save_trace``,
  fed back through ``replay`` — the SynergAI replay is checked
  bit-for-bit against the exporting run), (b) engine-popularity *drift*
  (``scenario(kind="drift")``: the offline-calibrated mix goes stale
  mid-trace), and (c) a *correlated-region outage*
  (``synth_failures(regions=..., correlation=...)``: a sampled fraction
  of a region's pools goes down simultaneously).
* ``bench_overload`` — goodput under 2x sustained overload with
  flapping regional failures and a WAN partition: uncontrolled vs
  ``OverloadController`` (doom shedding + per-region queue caps) on the
  identical trace and fault timeline.  Headline: controlled goodput
  >= 1.5x uncontrolled at bounded p99 queue depth
  (``overload_headline``, gated nightly).

Run standalone:  PYTHONPATH=src python benchmarks/scheduler_experiments.py
(see --help for the fleet/serving knobs; ``--json`` dumps the
fleet/serving/streaming bench outputs for CI artifacts)
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.baselines import (BestEffort, LeastRecentlyUsed,
                                  MostRecentlyUsed, RoundRobin,
                                  StrictRoundRobin)
from repro.core.job import make_experiment
from repro.core.metrics import summarize
from repro.core.offline import characterize
from repro.core.scheduler import SynergAI
from repro.core.simulator import Simulator
from repro.core.slo_mael import SloMael

POLICIES = [RoundRobin, StrictRoundRobin, LeastRecentlyUsed,
            MostRecentlyUsed, BestEffort, SloMael, SynergAI]
EXPERIMENTS = [("DL-FL", "DL", "FL"), ("DL-FH", "DL", "FH"),
               ("DH-FH", "DH", "FH")]


def run(cd=None, seeds=(1, 2, 3, 4, 5), emit=print):
    cd = cd or characterize()
    results = {}
    for exp, d, f in EXPERIMENTS:
        for P in POLICIES:
            agg = {"violations": 0, "waiting": [], "e2e": [], "p99": [],
                   "excess": [], "overhead": []}
            for seed in seeds:
                jobs = make_experiment(cd, d, f, seed=seed)
                s = summarize(Simulator(cd, P(), seed=seed).run(jobs))
                agg["violations"] += s["violations"]
                agg["waiting"].append(s["waiting_avg_s"])
                agg["e2e"].append(s["e2e_avg_s"])
                agg["p99"].append(s["e2e_p99_s"])
                agg["excess"].append(s["excess_avg_s"])
                agg["overhead"].append(s["overhead_avg_s"])
            results[(exp, P.name)] = agg
            emit(f"scheduler,{exp},{P.name},"
                 f"violations={agg['violations']},"
                 f"wait_s={np.mean(agg['waiting']):.1f},"
                 f"e2e_s={np.mean(agg['e2e']):.1f},"
                 f"p99_s={np.mean(agg['p99']):.1f},"
                 f"excess_s={np.mean(agg['excess']):.1f}")
    # headlines vs the paper
    v = lambda name: sum(results[(e, name)]["violations"]
                         for e, _, _ in EXPERIMENTS)
    base_names = ["RR", "SRR", "LRU", "MRU", "BE"]
    v_syn, v_mael = v("SynergAI"), v("SLO-MAEL")
    v_base = np.mean([v(n) for n in base_names])
    e_syn = np.mean([np.mean(results[(e, "SynergAI")]["excess"])
                     for e, _, _ in EXPERIMENTS])
    e_base = np.mean([np.mean(results[(e, n)]["excess"])
                      for e, _, _ in EXPERIMENTS for n in base_names])
    emit(f"scheduler_headline,slomael_over_synergai="
         f"{v_mael / max(1, v_syn):.2f}x,paper=2.4x")
    emit(f"scheduler_headline,baselines_over_synergai="
         f"{v_base / max(1, v_syn):.2f}x,paper=7.1x")
    emit(f"scheduler_headline,excess_baselines_over_synergai="
         f"{e_base / max(e_syn, 1e-9):.2f}x,paper=5.3x")
    return results


# ---------------------------------------------------------------------------
# fleet scale


def bench_fleet(cd=None, n_jobs=10_000, pools=(8, 28, 28),
                utilization=0.8, kind="mmpp", with_failures=True,
                emit=print):
    """The 10k-job x 64-pool scenario under every policy (event heap), then
    the old-vs-new simulator wall-clock comparison."""
    from repro.core.simulator import Simulator
    from repro.core.simulator_legacy import LegacySimulator
    from repro.core.workers import synth_fleet
    from repro.core.workload import scenario, synth_failures

    cd = cd or characterize()
    fleet = synth_fleet(*pools)
    W = len(fleet)
    jobs = scenario(cd, kind, n_jobs=n_jobs, fleet=fleet,
                    utilization=utilization, seed=0)
    span = jobs[-1].arrival
    failures = (synth_failures(fleet, span, mtbf_s=span, mttr_s=120.0,
                               seed=0) if with_failures else [])
    emit(f"fleet_scenario,{kind},jobs={n_jobs},pools={W},"
         f"span_s={span:.0f},failures={len(failures)}")
    walls = {}
    for P in POLICIES:
        t0 = time.perf_counter()
        res = Simulator(cd, P(), fleet=fleet, failures=failures,
                        seed=0).run(jobs)
        dt = time.perf_counter() - t0
        walls[(P.name, "event-heap")] = (dt, sum(r.violated for r in res))
        s = summarize(res)
        emit(f"fleet,{kind},{P.name},violations={s['violations']},"
             f"wait_s={s['waiting_avg_s']:.1f},p99_s={s['e2e_p99_s']:.1f},"
             f"wall_s={dt:.2f},jobs_per_s={n_jobs / dt:.0f}")
    # old vs new: the seed's tick-scanning loop (rescans every worker,
    # failure and running job per iteration) against the indexed event
    # heap, on the full trace.  The event-heap runs above (same trace,
    # same seed) already produced the "new" numbers; only the legacy loop
    # needs to run here.  SynergAI is scoring-bound so the engines tie
    # there; the cheap policies expose the loop overhead itself.
    for P in (SynergAI, RoundRobin, StrictRoundRobin):
        t0 = time.perf_counter()
        res = LegacySimulator(cd, P(), fleet=fleet, failures=failures,
                              seed=0).run(jobs)
        walls[(P.name, "legacy")] = (time.perf_counter() - t0,
                                     sum(r.violated for r in res))
        for label in ("legacy", "event-heap"):
            wall, viol = walls[(P.name, label)]
            emit(f"simulator,{label},{P.name},jobs={n_jobs},pools={W},"
                 f"wall_s={wall:.2f},violations={viol}")
        speedup = (walls[(P.name, "legacy")][0]
                   / max(walls[(P.name, "event-heap")][0], 1e-9))
        emit(f"simulator_headline,{P.name},"
             f"event_heap_speedup={speedup:.2f}x")
    return walls


def bench_serving(cd=None, n_jobs=2000, pools=(2, 5, 5),
                  utilization=1.3, kind="mmpp", emit=print):
    """Job-level vs batched serving on the same overloaded fleet scenario:
    what the scheduler gains once it can see continuous batching (the
    dominant real-world throughput lever; see docs/serving_bridge.md)."""
    from repro.core.simulator import Simulator
    from repro.core.workers import synth_fleet
    from repro.core.workload import scenario

    cd = cd or characterize()
    fleet = synth_fleet(*pools)
    out = {}
    for serving in ("job", "batched"):
        jobs = scenario(cd, kind, n_jobs=n_jobs, fleet=fleet,
                        utilization=utilization, seed=0, serving=serving)
        for P in (SynergAI, SloMael, RoundRobin):
            t0 = time.perf_counter()
            res = Simulator(cd, P(), fleet=fleet, seed=0,
                            serving=serving).run(jobs)
            dt = time.perf_counter() - t0
            s = summarize(res)
            out[(serving, P.name)] = s
            emit(f"serving,{kind},{serving},{P.name},"
                 f"violations={s['violations']},"
                 f"wait_s={s['waiting_avg_s']:.1f},"
                 f"p99_s={s['e2e_p99_s']:.1f},wall_s={dt:.2f}")
    v_job = out[("job", "SynergAI")]["violations"]
    v_bat = out[("batched", "SynergAI")]["violations"]
    emit(f"serving_headline,SynergAI,job_over_batched_violations="
         f"{v_job / max(1, v_bat):.2f}x,"
         f"p99_job_s={out[('job', 'SynergAI')]['e2e_p99_s']:.1f},"
         f"p99_batched_s={out[('batched', 'SynergAI')]['e2e_p99_s']:.1f}")
    return out


def bench_streaming(cd=None, n_jobs=1500, pools=(2, 5, 5),
                    utilization=1.3, kind="mmpp", streaming=(2.0, 2.5),
                    prefill_frac=0.4, emit=print):
    """Aggregated vs prefill/decode-disaggregated serving under streaming
    SLOs: the same overloaded trace with per-class TTFT/TPOT deadlines
    (``scenario(..., streaming=...)``) on a plain batched fleet vs one
    whose replicas are phase-tagged.  ``prefill_frac`` overprovisions the
    short latency-critical phase (0.4 vs the work's ~15% prefill share)
    so TTFT survives bursts — the classic disaggregation trade: first
    tokens come fast, decode capacity shrinks."""
    from repro.core.simulator import Simulator
    from repro.core.workers import synth_fleet
    from repro.core.workload import scenario

    cd = cd or characterize()
    fleets = {"aggregated": synth_fleet(*pools),
              "disaggregated": synth_fleet(*pools,
                                           disaggregate=prefill_frac)}
    out = {}
    for label, fleet in fleets.items():
        jobs = scenario(cd, kind, n_jobs=n_jobs, fleet=fleet,
                        utilization=utilization, seed=0,
                        serving="batched", streaming=streaming)
        for P in (SynergAI, SloMael, RoundRobin):
            t0 = time.perf_counter()
            res = Simulator(cd, P(), fleet=fleet, seed=0,
                            serving="batched").run(jobs)
            dt = time.perf_counter() - t0
            s = summarize(res)
            out[(label, P.name)] = s
            emit(f"streaming,{kind},{label},{P.name},"
                 f"ttft_violations={s['ttft_violations']},"
                 f"tpot_violations={s['tpot_violations']},"
                 f"violations={s['violations']},"
                 f"ttft_p99_s={s.get('ttft_p99_s', float('nan')):.2f},"
                 f"tpot_p99_ms={1e3 * s.get('tpot_p99_s', float('nan')):.2f},"
                 f"p99_s={s['e2e_p99_s']:.1f},wall_s={dt:.2f}")
    agg = out[("aggregated", "SynergAI")]
    dis = out[("disaggregated", "SynergAI")]
    emit(f"streaming_headline,SynergAI,"
         f"ttft_violations_agg={agg['ttft_violations']},"
         f"ttft_violations_disagg={dis['ttft_violations']},"
         f"agg_over_disagg="
         f"{agg['ttft_violations'] / max(1, dis['ttft_violations']):.2f}x,"
         f"ttft_p99_agg_s={agg.get('ttft_p99_s', float('nan')):.2f},"
         f"ttft_p99_disagg_s={dis.get('ttft_p99_s', float('nan')):.2f}")
    return out


def backlog_churn(cd, make_policy, J, pools, iters=40, churn=64,
                  tick=1.0, utilization=0.8):
    """The standing-backlog churn loop behind ``bench_sched_overhead``.

    Queues ``J`` jobs of a seeded MMPP trace on ``synth_fleet(*pools)``,
    runs one warm-up ``schedule`` call, then for each of ``iters`` ticks
    frees ``churn`` random workers, times one ``schedule`` call, applies
    its assignments (placed jobs leave the queue, their workers go busy)
    and injects ``churn`` fresh arrivals.  Every input is seeded, so two
    policies see the identical stream until their assignments differ.
    Returns ``(assignments, tick_seconds, policy)``: per tick the list of
    ``(job id, worker)`` pairs, the host wall-clock of each call, and
    the policy ``make_policy()`` built."""
    from repro.core.job import exec_time
    from repro.core.workers import synth_fleet
    from repro.core.workload import scenario

    fleet = synth_fleet(*pools)
    W = len(fleet)
    jobs = scenario(cd, "mmpp", n_jobs=J + iters * churn, fleet=fleet,
                    utilization=utilization, seed=0)
    queue = list(jobs[:J])
    reservoir = jobs[J:]
    now = queue[-1].arrival
    pol = make_policy()
    cl = Simulator(cd, pol, fleet=fleet, seed=0).cluster
    rng = np.random.default_rng(0)
    names = cl.arrays.names
    pol.schedule(now, queue, cl)        # warm caches / tracing
    ticks, log = [], []
    for i in range(iters):
        now += tick
        for wi in rng.choice(W, size=min(churn, W), replace=False):
            cl.workers[names[wi]].busy_until = now
        t0 = time.perf_counter()
        asg = pol.schedule(now, queue, cl)
        ticks.append(time.perf_counter() - t0)
        log.append([(a.job.id, a.worker) for a in asg])
        placed = set()
        for a in asg:
            cl.workers[a.worker].busy_until = (
                now + exec_time(a.entry, a.job.queries))
            placed.add(a.job.id)
        queue = [j for j in queue if j.id not in placed]
        fresh = reservoir[i * churn:(i + 1) * churn]
        for j in fresh:
            j.arrival = now
        queue.extend(fresh)
    return log, ticks, pol


def bench_sched_overhead(cd=None, sizes=((2_000, (8, 28, 28)),
                                         (10_000, (8, 28, 28))),
                         iters=40, churn=64, tick=1.0,
                         pallas_max_j=2_000, utilization=0.8,
                         only=None, strict_parity=False, emit=print):
    """Per-tick scheduler decision time under a standing MMPP backlog.

    ``backlog_churn`` keeps the queue depth at ~J while churning it
    exactly like the simulator does.  That makes the *incremental* cost
    visible: the cached variant re-scores only the churn, the uncached
    variant rebuilds the full [J, W] matrix every tick, and the
    device-resident ``pallas-resident`` variant ships only the churn's
    rows host->device and runs the whole decision as one fused dispatch.
    That variant runs compiled on a TPU backend and interpreted elsewhere
    (on CPU the kernel is emulated op-by-op — wall-clock is not the point
    there), capped at ``pallas_max_j``; a compiled run carries a
    ``-compiled`` suffix (read from ``pol.cache.interpret``) so its
    numbers land under their own regression keys.  The times are host
    wall-clock.

    The device variant's per-tick assignments are compared against the
    cached numpy variant's on the identical churn stream and recorded as
    ``assignments_match_cached``; ``strict_parity=True`` raises on any
    mismatch (the CI smoke contract).  ``only`` restricts the run to
    ``("cached", only)`` for the tier-1 smoke leg."""
    from repro.core.workers import synth_fleet

    cd = cd or characterize()
    variants = [
        ("uncached", lambda: SynergAI(incremental=False)),
        ("cached", lambda: SynergAI()),
        ("pallas-resident", lambda: SynergAI(device=True)),
    ]
    if only is not None:
        variants = [v for v in variants if v[0] in ("cached", only)]
    results = []
    for J, pools in sizes:
        W = len(synth_fleet(*pools))
        base = {}
        cached_log = None
        for name, mk in variants:
            if name.startswith("pallas") and J > pallas_max_j:
                continue
            asg_log, ticks, pol = backlog_churn(
                cd, mk, J, pools, iters=iters, churn=churn, tick=tick,
                utilization=utilization)
            if pol.device and not pol.cache.interpret:
                name += "-compiled"
            placed_total = sum(len({jid for jid, _ in a})
                               for a in asg_log)
            mean_ms = 1e3 * float(np.mean(ticks))
            p50_ms = 1e3 * float(np.median(ticks))
            rec = {"variant": name, "J": J, "W": W, "serving": "job",
                   "iters": iters, "churn": churn,
                   "mean_tick_ms": mean_ms, "p50_tick_ms": p50_ms,
                   "placed_per_tick": placed_total / iters}
            if name == "uncached":
                base[(J, W)] = mean_ms
            if (J, W) in base:
                rec["speedup_vs_uncached"] = base[(J, W)] / mean_ms
            if name == "cached":
                cached_log = asg_log
            if pol.device and cached_log is not None:
                match = asg_log == cached_log
                rec["assignments_match_cached"] = match
                if strict_parity and not match:
                    bad = next(i for i, (a, b)
                               in enumerate(zip(asg_log, cached_log))
                               if a != b)
                    raise RuntimeError(
                        f"{name} diverged from cached at tick {bad}: "
                        f"{asg_log[bad][:4]} != {cached_log[bad][:4]}")
            if pol.device:
                dc = pol.cache
                rec["hd_bytes_per_tick"] = dc.bytes_to_device / dc.ticks
                rec["rows_uploaded"] = dc.rows_uploaded
                rec["fail_masks"] = dc.fail_masks
                rec["flushes"] = dc.flushes
            results.append(rec)
            emit(f"sched_overhead,{name},J={J},W={W},"
                 f"mean_tick_ms={mean_ms:.2f},p50_tick_ms={p50_ms:.2f},"
                 f"speedup_vs_uncached="
                 f"{rec.get('speedup_vs_uncached', 1.0):.2f}x"
                 + (",parity="
                    + ("ok" if rec["assignments_match_cached"]
                       else "MISMATCH")
                    if "assignments_match_cached" in rec else ""))
    head = [r for r in results
            if r["variant"] == "cached" and r["J"] == 10_000]
    blob = {"schema": 1, "bench": "bench_sched_overhead",
            "configs": results}
    if head:
        blob["headline"] = {
            "J": head[0]["J"], "W": head[0]["W"],
            "cached_mean_tick_ms": head[0]["mean_tick_ms"],
            "speedup_cached_vs_uncached":
                head[0].get("speedup_vs_uncached", 1.0)}
        emit(f"sched_overhead_headline,J={head[0]['J']},"
             f"W={head[0]['W']},cached_vs_uncached="
             f"{head[0].get('speedup_vs_uncached', 1.0):.2f}x,target=5x")
    return blob


def bench_regions(cd=None, pools=(256, 896, 896), regions_sweep=(8, 16, 32),
                  J=4096, iters=12, churn=256, tick=1.0, utilization=0.8,
                  smoke=False, emit=print):
    """Flat vs hierarchical per-tick decision time at fleet scale.

    The same standing-backlog churn loop as ``bench_sched_overhead``
    (free ``churn`` workers, time one ``schedule`` call, apply, inject
    arrivals), run over a region-tagged fleet at each region count in
    ``regions_sweep``: ``flat`` is the incremental ``SynergAI`` scoring
    all W pools every tick, ``hier`` is ``HierarchicalSynergAI`` routing
    in O(k) and scoring only region slices.  Region tags are inert to
    the flat policy, so both variants face the identical workload.
    ``speedup_hier_vs_flat`` is hardware-independent (both sides
    measured in-process) and is what the nightly perf gate watches
    (``tools/check_perf_regression.py``, 4x floor at the headline
    config).  ``smoke=True`` shrinks everything to a seconds-long CI
    sanity leg (the ratio is meaningless at that size — the smoke leg
    only proves the bench runs)."""
    import numpy as np

    from repro.core.hierarchy import HierarchicalSynergAI
    from repro.core.job import exec_time
    from repro.core.workers import synth_fleet
    from repro.core.workload import scenario

    cd = cd or characterize()
    if smoke:
        pools, regions_sweep = (8, 28, 28), (4,)
        J, iters, churn = 500, 4, 32
    variants = [("flat", lambda: SynergAI()),
                ("hier", lambda: HierarchicalSynergAI())]
    results = []
    for k in regions_sweep:
        fleet = synth_fleet(*pools, regions=k)
        W = len(fleet)
        base = {}
        for name, mk in variants:
            jobs = scenario(cd, "mmpp", n_jobs=J + iters * churn,
                            fleet=fleet, utilization=utilization, seed=0)
            queue = list(jobs[:J])
            reservoir = jobs[J:]
            now = queue[-1].arrival
            pol = mk()
            sim = Simulator(cd, pol, fleet=fleet, seed=0)
            cl = sim.cluster
            rng = np.random.default_rng(0)
            names = cl.arrays.names
            for j in queue:
                pol.on_arrival(j, cl, now)      # the simulator's hook
            pol.schedule(now, queue, cl)        # warm caches / tables
            ticks, placed_total = [], 0
            for i in range(iters):
                now += tick
                for wi in rng.choice(W, size=min(churn, W),
                                     replace=False):
                    cl.workers[names[wi]].busy_until = now
                t0 = time.perf_counter()
                asg = pol.schedule(now, queue, cl)
                ticks.append(time.perf_counter() - t0)
                placed = set()
                for a in asg:
                    cl.workers[a.worker].busy_until = (
                        now + a.xfer_s
                        + exec_time(a.entry, a.job.queries))
                    placed.add(a.job.id)
                placed_total += len(placed)
                queue = [j for j in queue if j.id not in placed]
                fresh = reservoir[i * churn:(i + 1) * churn]
                for j in fresh:
                    j.arrival = now
                    pol.on_arrival(j, cl, now)
                queue.extend(fresh)
            mean_ms = 1e3 * float(np.mean(ticks))
            p50_ms = 1e3 * float(np.median(ticks))
            rec = {"variant": name, "J": J, "W": W, "serving": "job",
                   "regions": k, "iters": iters, "churn": churn,
                   "mean_tick_ms": mean_ms, "p50_tick_ms": p50_ms,
                   "placed_per_tick": placed_total / iters}
            if name == "flat":
                base[(J, W, k)] = mean_ms
            else:
                rec["speedup_hier_vs_flat"] = base[(J, W, k)] / mean_ms
                rec["spills"] = pol.spills
            results.append(rec)
            emit(f"regions,{name},J={J},W={W},k={k},"
                 f"mean_tick_ms={mean_ms:.2f},p50_tick_ms={p50_ms:.2f},"
                 f"speedup_hier_vs_flat="
                 f"{rec.get('speedup_hier_vs_flat', 1.0):.2f}x")
    blob = {"schema": 1, "bench": "bench_regions", "configs": results}
    if not smoke:
        head = [r for r in results if r["variant"] == "hier"
                and r["regions"] >= 16] or \
               [r for r in results if r["variant"] == "hier"]
        if head:
            h = head[0]
            blob["regions_headline"] = {
                "J": h["J"], "W": h["W"], "regions": h["regions"],
                "hier_mean_tick_ms": h["mean_tick_ms"],
                "speedup_hier_vs_flat": h["speedup_hier_vs_flat"]}
            emit(f"regions_headline,J={h['J']},W={h['W']},"
                 f"k={h['regions']},hier_vs_flat="
                 f"{h['speedup_hier_vs_flat']:.2f}x,target=4x")
    return blob


def bench_traces(cd=None, n_jobs=1500, pools=(2, 5, 5), utilization=1.3,
                 n_regions=3, correlation=0.6, emit=print):
    """The trace-driven scenarios under every policy: a replayed mmpp
    overload trace (bit-for-bit against the exporting run), engine-
    popularity drift, and a correlated multi-region outage."""
    import os
    import tempfile

    from repro.core.simulator import Simulator
    from repro.core.workers import synth_fleet
    from repro.core.workload import (replay, save_trace, scenario,
                                     synth_failures)

    cd = cd or characterize()
    fleet = synth_fleet(*pools, regions=n_regions)
    out = {}

    def sweep(section, jobs, failures=()):
        for P in POLICIES:
            t0 = time.perf_counter()
            res = Simulator(cd, P(), fleet=fleet, failures=failures,
                            seed=0).run(jobs)
            dt = time.perf_counter() - t0
            s = summarize(res)
            out[(section, P.name)] = s
            emit(f"traces,{section},{P.name},"
                 f"violations={s['violations']},"
                 f"wait_s={s['waiting_avg_s']:.1f},"
                 f"p99_s={s['e2e_p99_s']:.1f},wall_s={dt:.2f}")

    # (a) replay: export a completed run, feed it back, pin equality
    jobs = scenario(cd, "mmpp", n_jobs=n_jobs, fleet=fleet,
                    utilization=utilization, seed=0)
    base = Simulator(cd, SynergAI(), fleet=fleet, seed=0).run(jobs)
    fd, path = tempfile.mkstemp(suffix=".jsonl", prefix="synergai_trace_")
    os.close(fd)
    try:
        save_trace(path, base)
        replayed = replay(path)
        res_r = Simulator(cd, SynergAI(), fleet=fleet, seed=0).run(replayed)
        key = lambda rs: sorted((r.job.id, r.worker, r.start, r.end,
                                 r.ttft, r.tpot) for r in rs)
        exact = key(base) == key(res_r)
        out[("replay", "exact")] = {"replay_exact": exact,
                                    "records": len(replayed)}
        emit(f"traces,replay,exact={exact},records={len(replayed)}")
        sweep("replay", replayed)
    finally:
        os.unlink(path)

    # (b) drift: the capacity-proportional mix flips edge<->heavy shares
    drift_jobs = scenario(cd, "drift", n_jobs=n_jobs, fleet=fleet,
                          utilization=utilization, seed=0)
    sweep("drift", drift_jobs)

    # (c) correlated-region outage on the replayed trace's timeline
    span = jobs[-1].arrival
    failures = synth_failures(fleet, span, mtbf_s=span, mttr_s=180.0,
                              seed=0, regions=True,
                              correlation=correlation)
    emit(f"traces,outage,regions={n_regions},correlation={correlation},"
         f"failure_events={len(failures)}")
    sweep("outage", jobs, failures=failures)

    # (d) drift + online re-characterization: unmodeled pool slowdowns
    # (synth_degradations) under the drift mix, SynergAI with a stale
    # offline profile vs the online loop vs the true-factor oracle
    from repro.core.recharacterize import OnlineRecharacterizer
    from repro.core.simulator import Cluster
    from repro.core.workload import synth_degradations
    degs = synth_degradations(fleet, drift_jobs[-1].arrival, factor=5.0,
                              fraction=0.35, prefix="edge", seed=0)
    truth = {d.worker: d.factor for d in degs}
    for name, rc in (("stale", None), ("online", OnlineRecharacterizer()),
                     ("oracle", None)):
        if name == "oracle":
            rc = OnlineRecharacterizer(detect=False)
            rc.seed(Cluster(cd, list(fleet)), worker_factors=truth)
        t0 = time.perf_counter()
        res = Simulator(cd, SynergAI(recharacterizer=rc), fleet=fleet,
                        degradations=degs, seed=0).run(list(drift_jobs))
        dt = time.perf_counter() - t0
        s = summarize(res)
        out[("drift+recharacterize", name)] = s
        extra = (f",refreshes={rc.refreshes}"
                 if rc is not None and name == "online" else "")
        emit(f"traces,drift+recharacterize,{name},"
             f"violations={s['violations']},"
             f"wait_s={s['waiting_avg_s']:.1f},"
             f"p99_s={s['e2e_p99_s']:.1f},wall_s={dt:.2f}{extra}")

    v = lambda section, name: out[(section, name)]["violations"]
    base_names = ["RR", "SRR", "LRU", "MRU", "BE"]
    for section in ("replay", "drift", "outage"):
        v_base = np.mean([v(section, n) for n in base_names])
        emit(f"traces_headline,{section},baselines_over_synergai="
             f"{v_base / max(1, v(section, 'SynergAI')):.2f}x")
    emit(f"traces_headline,drift+recharacterize,stale_over_online="
         f"{v('drift+recharacterize', 'stale') / max(1, v('drift+recharacterize', 'online')):.2f}x")
    return out


def bench_drift_recovery(cd=None, n_jobs=6000, pools=(2, 5, 5),
                         n_regions=3, utilization=0.6, factor=5.0,
                         fraction=0.35, smoke=False, emit=print):
    """Violations under unmodeled physics drift, with and without the
    online re-characterization loop — the committed ``drift_headline``
    the nightly perf gate enforces.

    A third of the way into a drift-mix trace, ``fraction`` of the edge
    pools silently degrade to ``factor``x their characterized service
    time (``synth_degradations`` — thermal throttling, a colocated
    tenant; nothing tells the policies).  Three SynergAI runs on the
    identical trace and degradation timeline:

    - ``stale``  — the offline profile, trusted forever (the paper's
      open loop; keeps placing work on pools it believes are fast).
    - ``online`` — ``OnlineRecharacterizer``: residual-triggered
      refreshes re-fit the per-(engine, worker) effective rates and
      placement routes around the slow pools within a few completions.
    - ``oracle`` — the true factors installed at t=0 with detection
      muted: the floor the online loop converges toward.

    The headline ``violation_ratio_stale_vs_online`` is deterministic
    (fixed seeds, fixed timeline) and hardware-independent, so the gate
    fails on any code change that erodes recovery by >30% — and on a
    drop below the 5x acceptance floor.  ``smoke=True`` shrinks the
    trace to a seconds-long CI sanity leg (the ratio is noise at that
    size; the smoke leg only proves the bench runs)."""
    from repro.core.recharacterize import OnlineRecharacterizer
    from repro.core.simulator import Cluster
    from repro.core.workers import synth_fleet
    from repro.core.workload import scenario, synth_degradations

    cd = cd or characterize()
    if smoke:
        n_jobs = 800
    fleet = synth_fleet(*pools, regions=n_regions)
    W = len(fleet)
    jobs = scenario(cd, "drift", n_jobs=n_jobs, fleet=fleet,
                    utilization=utilization, seed=0)
    degs = synth_degradations(fleet, jobs[-1].arrival, factor=factor,
                              fraction=fraction, prefix="edge", seed=0)
    truth = {d.worker: d.factor for d in degs}
    blob = {"schema": 1, "bench": "bench_drift_recovery", "configs": []}
    viol = {}
    for name in ("stale", "online", "oracle"):
        rc = None
        if name == "online":
            rc = OnlineRecharacterizer()
        elif name == "oracle":
            rc = OnlineRecharacterizer(detect=False)
            rc.seed(Cluster(cd, list(fleet)), worker_factors=truth)
        t0 = time.perf_counter()
        res = Simulator(cd, SynergAI(recharacterizer=rc), fleet=fleet,
                        degradations=degs, seed=0).run(list(jobs))
        dt = time.perf_counter() - t0
        s = summarize(res)
        viol[name] = s["violations"]
        cfg = {"variant": f"drift-{name}", "J": n_jobs, "W": W,
               "serving": "job", "factor": factor, "fraction": fraction,
               "violations": s["violations"],
               "wait_avg_s": s["waiting_avg_s"],
               "e2e_p99_s": s["e2e_p99_s"], "wall_s": dt}
        if name == "online":
            cfg["refreshes"] = rc.refreshes
        blob["configs"].append(cfg)
        emit(f"drift_recovery,{name},J={n_jobs},W={W},"
             f"violations={s['violations']},wall_s={dt:.2f}")
    ratio = viol["stale"] / max(1, viol["online"])
    for cfg in blob["configs"]:
        if cfg["variant"] == "drift-online":
            cfg["violation_ratio_stale_vs_online"] = ratio
    if not smoke:
        blob["drift_headline"] = {
            "J": n_jobs, "W": W, "factor": factor, "fraction": fraction,
            "violations_stale": viol["stale"],
            "violations_online": viol["online"],
            "violations_oracle": viol["oracle"],
            "violation_ratio_stale_vs_online": ratio}
    emit(f"drift_recovery_headline,stale_over_online={ratio:.2f}x,"
         f"oracle_violations={viol['oracle']}")
    return blob


def bench_energy(cd=None, n_jobs=2000, pools=(2, 5, 5), n_regions=3,
                 utilization=0.6, energy_weight=1e-2,
                 hier_energy_weight=1e-1, smoke=False, emit=print):
    """Energy/carbon-aware objective vs the energy-blind scheduler on one
    trace — the committed ``energy_headline`` the nightly perf gate
    enforces.

    Five runs of the identical region-tagged MMPP trace: flat SynergAI
    energy-blind / energy-weighted / carbon-weighted (the same weight
    scaled by a synthetic per-region diurnal ``CarbonTrace``), then
    ``HierarchicalSynergAI`` blind vs carbon-aware (the weight also
    steers the O(k) router toward the currently-cleanest region).  In
    this fleet the cloud pod is the per-query energy hog (556 J/job vs
    280-420 J on the edge slices), so the aware runs pull work *onto*
    edge: edge joules rise while fleet-wide active energy and carbon
    fall — the headline rides on *active* joules (``total - idle``;
    the idle floor is a span-fixed constant no placement policy can
    move while every pool stays powered, and it is what the post-hoc
    carbon accounting meters too) and on ``carbon_kg``, with
    edge/total/idle breakdowns reported per config.

    The energy term enters only the placement *ranking* (acceptability,
    doom and urgency stay purely time-derived), so QoS cannot collapse
    by construction — the headline run shows the aware variants with
    *fewer* deadline misses than blind, and the gate holds
    ``violation_overhead`` at +10%.  Carbon is accounted post-hoc per
    job at the grid intensity of the serving pool's region at the job's
    service midpoint, so time-shifted *and* region-shifted placements
    both register.  Everything is deterministic (fixed seeds, no
    timing in any gated number); ``smoke=True`` shrinks the trace to a
    seconds-long CI sanity leg (reductions are noise at that size — the
    smoke leg only proves the bench runs)."""
    from repro.core.energy import offload_fraction
    from repro.core.hierarchy import HierarchicalSynergAI
    from repro.core.workers import synth_fleet
    from repro.core.workload import CarbonTrace, scenario

    cd = cd or characterize()
    if smoke:
        n_jobs = min(n_jobs, 300)
    fleet = synth_fleet(*pools, regions=n_regions)
    W = len(fleet)
    jobs = scenario(cd, "mmpp", n_jobs=n_jobs, fleet=fleet,
                    utilization=utilization, seed=3)
    regions = sorted({w.region for w in fleet})
    # two diurnal periods over the trace: the cleanest region moves
    trace = CarbonTrace.synth(regions, period_s=2.0 * jobs[-1].arrival)

    def carbon_kg(results, cluster):
        grams = 0.0
        for r in results:
            ent = cd.optimal(r.job.engine, r.worker)
            region = cluster.workers[r.worker].pool.region
            g_kwh = trace.intensity(region, 0.5 * (r.start + r.end))
            grams += ent.power_w * r.exec_s / 3.6e6 * g_kwh
        return grams / 1e3

    variants = [
        ("flat-blind", 0.0, lambda: SynergAI()),
        ("flat-energy", energy_weight,
         lambda: SynergAI(energy_weight=energy_weight)),
        ("flat-carbon", energy_weight,
         lambda: SynergAI(energy_weight=energy_weight, carbon=trace)),
        ("hier-blind", 0.0, lambda: HierarchicalSynergAI()),
        ("hier-carbon", hier_energy_weight,
         lambda: HierarchicalSynergAI(energy_weight=hier_energy_weight,
                                      carbon=trace)),
    ]
    blob = {"schema": 1, "bench": "bench_energy", "configs": []}
    stats = {}
    for name, ew, mk in variants:
        t0 = time.perf_counter()
        sim = Simulator(cd, mk(), fleet=fleet, seed=3)
        res = sim.run(list(jobs))
        dt = time.perf_counter() - t0
        s = summarize(res)
        ws = sim.cluster.workers.values()
        edge_j = sum(w.energy_j for w in ws if w.pool.is_edge)
        total_j = sum(w.total_energy_j for w in ws)
        idle_j = sum(w.idle_energy_j for w in ws)
        kg = carbon_kg(res, sim.cluster)
        stats[name] = (s["violations"], total_j - idle_j, kg)
        cfg = {"variant": f"energy-{name}", "J": n_jobs, "W": W,
               "serving": "job", "regions": n_regions,
               "energy_weight": ew, "violations": s["violations"],
               "edge_energy_mj": edge_j / 1e6,
               "total_energy_mj": total_j / 1e6,
               "idle_energy_mj": idle_j / 1e6, "carbon_kg": kg,
               "offload": offload_fraction(res, sim.cluster),
               "wall_s": dt}
        blob["configs"].append(cfg)
        emit(f"energy,{name},J={n_jobs},W={W},"
             f"violations={s['violations']},"
             f"total_mj={total_j / 1e6:.2f},carbon_kg={kg:.3f},"
             f"offload={cfg['offload']:.2f}")
    v_blind, e_blind, c_blind = stats["flat-blind"]
    v_energy, e_energy, _ = stats["flat-energy"]
    v_carbon, _, c_carbon = stats["flat-carbon"]
    hv_blind, _, hc_blind = stats["hier-blind"]
    hv_carbon, _, hc_carbon = stats["hier-carbon"]
    e_cut = 1.0 - e_energy / e_blind
    c_cut = 1.0 - c_carbon / c_blind
    h_cut = 1.0 - hc_carbon / hc_blind
    overhead = (max(v_energy, v_carbon) - v_blind) / max(1, v_blind)
    for cfg in blob["configs"]:
        if cfg["variant"] == "energy-flat-energy":
            cfg["energy_reduction_vs_blind"] = e_cut
        elif cfg["variant"] == "energy-flat-carbon":
            cfg["carbon_reduction_vs_blind"] = c_cut
        elif cfg["variant"] == "energy-hier-carbon":
            cfg["carbon_reduction_vs_blind"] = h_cut
    if not smoke:
        blob["energy_headline"] = {
            "J": n_jobs, "W": W, "regions": n_regions,
            "energy_weight": energy_weight,
            "violations_blind": v_blind, "violations_energy": v_energy,
            "violations_carbon": v_carbon,
            "violations_hier_blind": hv_blind,
            "violations_hier_carbon": hv_carbon,
            "energy_reduction": e_cut, "carbon_reduction": c_cut,
            "hier_carbon_reduction": h_cut,
            "violation_overhead": overhead}
    emit(f"energy_headline,energy_cut={e_cut:.3f},carbon_cut={c_cut:.3f},"
         f"hier_carbon_cut={h_cut:.3f},violation_overhead={overhead:.3f}")
    return blob


def bench_overload(cd=None, n_jobs=4000, pools=(2, 5, 5), n_regions=3,
                   utilization=2.0, patience=8.0, queue_cap=12,
                   retry_budget=3, smoke=False, emit=print):
    """Goodput under sustained overload with and without the
    ``OverloadController`` — the committed ``overload_headline`` the
    nightly perf gate enforces.

    The fleet is driven at ``utilization`` ~= 2x its capacity (an MMPP
    trace it can never drain), with flapping regional failures
    (``synth_failures(..., flap=3)``: pools oscillate between apparent
    health and crash-restart, killing whatever was placed during the
    up-phase) and a WAN partition severing one region pair for the
    middle half of the trace (``LinkFailureEvent`` — no spillover, no
    cross-region KV).  Clients are impatient (``patience`` x t_qos) and
    kills retry under an exponential-backoff budget, so both runs reach
    a terminal outcome for every job.  Two ``HierarchicalSynergAI`` runs
    of the identical trace and fault timeline:

    - ``uncontrolled`` — no controller: every job is scheduled until it
      completes (usually violated), abandons, or exhausts its retries.
      The queue grows without bound and service effort smears across
      jobs that are already past their deadline.
    - ``controlled`` — ``OverloadController(queue_cap=...)``: certainly-
      doomed jobs (``t_rem < min_est``, the score cache's own bound) are
      shed on sight and each region's queue is capped to the cap-most-
      schedulable jobs, so servers only run work that can still meet
      QoS.

    The headline ``goodput_ratio_controlled_vs_uncontrolled`` (within-
    QoS completions per second, ``metrics.summarize``'s ``goodput_jps``)
    must hold >= 1.5x with the controlled run's p99 queue depth under
    ``queue_depth_bound`` — shedding buys *useful* completions, not just
    a shorter queue.  Deterministic (fixed seeds, no timing in any gated
    number); ``smoke=True`` shrinks the trace to a seconds-long CI
    sanity leg (ratios are noise at that size — the smoke leg only
    proves the bench runs)."""
    from repro.core.hierarchy import HierarchicalSynergAI
    from repro.core.metrics import OUTCOMES
    from repro.core.overload import OverloadController
    from repro.core.simulator import LinkFailureEvent
    from repro.core.workers import synth_fleet
    from repro.core.workload import regional_scenario, synth_failures

    cd = cd or characterize()
    if smoke:
        n_jobs = min(n_jobs, 400)
    fleet = synth_fleet(*pools, regions=n_regions)
    W = len(fleet)
    jobs = regional_scenario(cd, "mmpp", n_jobs=n_jobs, fleet=fleet,
                             utilization=utilization, seed=0,
                             patience=patience)
    span = jobs[-1].arrival
    fails = synth_failures(fleet, span, mtbf_s=span / 2.0,
                           mttr_s=span / 12.0, seed=0, regions=True,
                           correlation=0.5, flap=3)
    links = [LinkFailureEvent("r0", "r1", 0.25 * span, 0.5 * span)]
    depth_bound = 6 * queue_cap * n_regions
    blob = {"schema": 1, "bench": "bench_overload", "configs": []}
    stats = {}
    for name in ("uncontrolled", "controlled"):
        ctrl = (OverloadController(queue_cap=queue_cap)
                if name == "controlled" else None)
        t0 = time.perf_counter()
        sim = Simulator(cd, HierarchicalSynergAI(overload=ctrl),
                        fleet=fleet, failures=fails, link_failures=links,
                        retry_budget=retry_budget, seed=0)
        res = sim.run(list(jobs))
        dt = time.perf_counter() - t0
        s = summarize(res)
        p99 = float(np.percentile(sim.queue_depths, 99))
        stats[name] = (s["goodput_jps"], p99)
        cfg = {"variant": f"overload-{name}", "J": n_jobs, "W": W,
               "serving": "job", "regions": n_regions,
               "utilization": utilization, "goodput_jps": s["goodput_jps"],
               "queue_depth_p99": p99, "wall_s": dt}
        for o in OUTCOMES:
            cfg[o] = s[o]
        if ctrl is not None:
            cfg["shed_doom_total"] = ctrl.shed_doom_total
            cfg["shed_backpressure_total"] = ctrl.shed_backpressure_total
        blob["configs"].append(cfg)
        emit(f"overload,{name},J={n_jobs},W={W},"
             f"goodput_jps={s['goodput_jps']:.3f},depth_p99={p99:.0f},"
             + ",".join(f"{o}={s[o]}" for o in OUTCOMES)
             + f",wall_s={dt:.2f}")
    g_un, _ = stats["uncontrolled"]
    g_ct, p99_ct = stats["controlled"]
    ratio = g_ct / max(g_un, 1e-12)
    for cfg in blob["configs"]:
        if cfg["variant"] == "overload-controlled":
            cfg["goodput_ratio_controlled_vs_uncontrolled"] = ratio
    if not smoke:
        blob["overload_headline"] = {
            "J": n_jobs, "W": W, "regions": n_regions,
            "utilization": utilization, "queue_cap": queue_cap,
            "goodput_uncontrolled_jps": g_un,
            "goodput_controlled_jps": g_ct,
            "goodput_ratio_controlled_vs_uncontrolled": ratio,
            "queue_depth_p99_controlled": p99_ct,
            "queue_depth_bound": depth_bound}
    emit(f"overload_headline,controlled_over_uncontrolled={ratio:.2f}x,"
         f"depth_p99={p99_ct:.0f}/{depth_bound}")
    return blob


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--jobs", type=int, default=10_000,
                   help="fleet-scale trace length (bench_fleet)")
    p.add_argument("--pools", type=int, nargs=3, default=(8, 28, 28),
                   metavar=("CLOUD", "EDGE_LG", "EDGE_SM"),
                   help="synth_fleet replica counts per archetype")
    p.add_argument("--kind", default="mmpp",
                   help="scenario preset: poisson | mmpp | diurnal | "
                        "flash | multi-tenant")
    p.add_argument("--skip-paper", action="store_true",
                   help="skip the 24-job paper experiments")
    p.add_argument("--skip-serving", action="store_true",
                   help="skip the job-level vs batched serving-bridge "
                        "comparison (scenario(..., serving='batched'))")
    p.add_argument("--skip-streaming", action="store_true",
                   help="skip the streaming-QoS aggregated vs "
                        "disaggregated comparison (bench_streaming)")
    p.add_argument("--skip-traces", action="store_true",
                   help="skip the trace-driven scenarios (replay / "
                        "drift / correlated-region outage, bench_traces)")
    p.add_argument("--skip-fleet", action="store_true",
                   help="skip the fleet-scale bench_fleet run")
    p.add_argument("--skip-sched", action="store_true",
                   help="skip the per-tick scheduler-overhead bench "
                        "(bench_sched_overhead)")
    p.add_argument("--sched-big", action="store_true",
                   help="extend bench_sched_overhead to the 50k-job x "
                        "256-pool sweep (numpy backends only)")
    p.add_argument("--sched-smoke", metavar="VARIANT", default=None,
                   help="run bench_sched_overhead as a small strict-"
                        "parity smoke of VARIANT (e.g. pallas-resident) "
                        "against the cached numpy path — seconds; the "
                        "tier-1 CI sanity leg; exits nonzero on any "
                        "assignment divergence")
    p.add_argument("--sched-json", metavar="PATH", default=None,
                   help="write the bench_sched_overhead + bench_regions "
                        "results as JSON (the BENCH_SCHED.json schema; "
                        "nightly CI gates it with "
                        "tools/check_perf_regression.py)")
    p.add_argument("--skip-regions", action="store_true",
                   help="skip the flat vs hierarchical region bench "
                        "(bench_regions)")
    p.add_argument("--regions-smoke", action="store_true",
                   help="run bench_regions at smoke size only (seconds; "
                        "the tier-1 CI sanity leg)")
    p.add_argument("--skip-drift", action="store_true",
                   help="skip the stale vs online re-characterization "
                        "drift-recovery bench (bench_drift_recovery)")
    p.add_argument("--drift-smoke", action="store_true",
                   help="run bench_drift_recovery at smoke size only "
                        "(seconds; the tier-1 CI sanity leg)")
    p.add_argument("--skip-energy", action="store_true",
                   help="skip the energy/carbon-aware vs energy-blind "
                        "objective bench (bench_energy)")
    p.add_argument("--energy-smoke", action="store_true",
                   help="run bench_energy at smoke size only (seconds; "
                        "the tier-1 CI sanity leg)")
    p.add_argument("--skip-overload", action="store_true",
                   help="skip the overload-control goodput bench "
                        "(bench_overload)")
    p.add_argument("--overload-smoke", action="store_true",
                   help="run bench_overload at smoke size only (seconds; "
                        "the tier-1 CI sanity leg)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="dump the serving/streaming bench summaries as "
                        "JSON (CI artifact)")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cd = characterize()
    blob = {}
    if not args.skip_paper:
        print("# paper experiments (Fig. 7-10)")
        run(cd, seeds=(1, 2, 3))
    sched = None
    if args.sched_smoke:
        print(f"# scheduler overhead smoke: cached vs {args.sched_smoke}"
              " (strict parity)")
        sched = bench_sched_overhead(
            cd, sizes=((256, (2, 3, 3)),), iters=6, churn=16,
            only=args.sched_smoke, strict_parity=True)
    elif not args.skip_sched:
        print("# scheduler overhead: uncached vs score-cache vs device")
        sizes = [(2_000, (8, 28, 28)), (10_000, (8, 28, 28))]
        if args.sched_big:
            sizes.append((50_000, (86, 85, 85)))
        sched = bench_sched_overhead(cd, sizes=tuple(sizes))
    if not args.skip_regions:
        print("# region sharding: flat vs hierarchical scheduler")
        reg = bench_regions(cd, smoke=args.regions_smoke)
        if sched is None:
            sched = reg
        else:
            sched["configs"].extend(reg["configs"])
            if "regions_headline" in reg:
                sched["regions_headline"] = reg["regions_headline"]
    if not args.skip_drift:
        print("# drift recovery: stale profile vs online "
              "re-characterization vs oracle")
        drift = bench_drift_recovery(cd, smoke=args.drift_smoke)
        if sched is None:
            sched = drift
        else:
            sched["configs"].extend(drift["configs"])
            if "drift_headline" in drift:
                sched["drift_headline"] = drift["drift_headline"]
    if not args.skip_energy:
        print("# energy/carbon objective: aware vs energy-blind")
        ene = bench_energy(cd, smoke=args.energy_smoke)
        if sched is None:
            sched = ene
        else:
            sched["configs"].extend(ene["configs"])
            if "energy_headline" in ene:
                sched["energy_headline"] = ene["energy_headline"]
    if not args.skip_overload:
        print("# overload control: controlled vs uncontrolled goodput")
        ov = bench_overload(cd, smoke=args.overload_smoke)
        if sched is None:
            sched = ov
        else:
            sched["configs"].extend(ov["configs"])
            if "overload_headline" in ov:
                sched["overload_headline"] = ov["overload_headline"]
    if args.sched_json and sched is not None:
        import json
        with open(args.sched_json, "w") as f:
            json.dump(sched, f, indent=1)
        print(f"# wrote {args.sched_json}")
    if not args.skip_serving:
        print("# serving bridge: job-level vs batched (mmpp overload)")
        blob["serving"] = bench_serving(cd)
    if not args.skip_streaming:
        print("# streaming QoS: aggregated vs disaggregated pools")
        blob["streaming"] = bench_streaming(cd)
    if not args.skip_traces:
        print("# trace-driven scenarios: replay / drift / region outage")
        blob["traces"] = bench_traces(cd)
    if not args.skip_fleet:
        print(f"# fleet scale ({args.kind})")
        bench_fleet(cd, n_jobs=args.jobs, pools=tuple(args.pools),
                    kind=args.kind)
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump({bench: {"/".join(k): v for k, v in d.items()}
                       for bench, d in blob.items()}, f, indent=1)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
