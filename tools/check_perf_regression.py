"""Gate scheduler-decision perf against the committed baseline.

Compares a fresh ``bench_sched_overhead`` JSON (written by
``benchmarks/scheduler_experiments.py --sched-json``) against the
committed ``BENCH_SCHED.json`` baseline and fails (exit 1) if per-tick
decision time regressed by more than ``--threshold`` (default 30%).

CI runners differ wildly in absolute speed, so the default gate compares
the *hardware-independent* ``speedup_vs_uncached`` ratios: both sides of
that ratio are measured in the same process on the same machine, so a
drop means the incremental path itself got slower relative to the
full-matrix rebuild — a real regression, not runner noise.  Pass
``--absolute`` to additionally gate the raw ``mean_tick_ms`` numbers
(useful when baseline and fresh run on pinned identical hardware).

The headline floors (cached >= 5x uncached at the 10k-job x 64-pool
backlog; hierarchical >= 4x flat at the region-sharded W=2048 fleet,
``regions_headline`` from ``bench_regions``; stale-profile violations
>= 5x online-loop violations under unmodeled drift, ``drift_headline``
from ``bench_drift_recovery``; energy-or-carbon-aware cut >= 20% at
<= +10% extra violations, ``energy_headline`` from ``bench_energy``;
controlled >= 1.5x uncontrolled goodput under 2x sustained overload at
a bounded p99 queue depth, ``overload_headline`` from
``bench_overload``) are always enforced when the fresh run contains
those configs.  ``speedup_hier_vs_flat`` entries are gated
exactly like ``speedup_vs_uncached`` — both sides measured in-process,
so the ratio is hardware-independent.  The drift ratio is not even a
timing: fixed seeds and a fixed degradation timeline make the
violation counts deterministic, so any drift at all is a code change.

``pallas-resident`` variant keys (the device-resident tick,
``SynergAI(device=True)``, and their ``-compiled`` twins from a TPU run)
are **parity-gated, ratio-tracked**: an ``assignments_match_cached: false``
in the fresh run fails the gate unconditionally, while their speed
ratios are printed (``trk``) but never floored — interpret-mode
wall-clock is an emulation artifact, and no compiled accelerator
baseline is committed yet.

Usage:  python tools/check_perf_regression.py BENCH_SCHED.json fresh.json
"""

from __future__ import annotations

import argparse
import json
import sys

HEADLINE_FLOOR = 5.0        # cached vs uncached at J=10k, W=64
REGIONS_FLOOR = 4.0         # hierarchical vs flat at W=2048, k>=16
DRIFT_FLOOR = 5.0           # stale vs online violations under drift
ENERGY_FLOOR = 0.20         # aware-vs-blind energy *or* carbon cut
ENERGY_VIOL_CEIL = 0.10     # allowed extra QoS violations, relative
OVERLOAD_FLOOR = 1.5        # controlled vs uncontrolled goodput

# the hardware-independent per-config ratios the gate watches
_SPEEDUPS = ("speedup_vs_uncached", "speedup_hier_vs_flat",
             "violation_ratio_stale_vs_online",
             "energy_reduction_vs_blind", "carbon_reduction_vs_blind",
             "goodput_ratio_controlled_vs_uncontrolled")


def _index(blob):
    return {(c["variant"], c["J"], c["W"], c.get("serving", "job"),
             c.get("regions", 0)): c
            for c in blob.get("configs", [])}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("baseline", help="committed BENCH_SCHED.json")
    p.add_argument("fresh", help="freshly measured bench JSON")
    p.add_argument("--threshold", type=float, default=0.30,
                   help="allowed relative regression (default 0.30)")
    p.add_argument("--absolute", action="store_true",
                   help="also gate raw mean_tick_ms (pinned hardware)")
    args = p.parse_args(argv)
    with open(args.baseline) as f:
        base = _index(json.load(f))
    with open(args.fresh) as f:
        fresh_blob = json.load(f)
    fresh = _index(fresh_blob)

    failures = []
    for key, fc in fresh.items():
        # the device-resident variants are parity-gated: a recorded
        # assignment divergence from the cached numpy path fails the
        # gate on its own, baseline or not
        if fc.get("assignments_match_cached") is False:
            print(f"FAIL {key}: assignments diverged from the cached "
                  f"numpy path")
            failures.append(
                f"{key}: assignments_match_cached is False — the "
                f"device path lost bit-for-bit parity")
        # pallas-resident speed ratios are *tracked*, never floored:
        # interpret-mode wall-clock is an emulation artifact, and no
        # compiled accelerator baseline is committed yet
        tracked = key[0].startswith("pallas-resident")
        bc = base.get(key)
        if bc is None:
            print(f"note {key}: no baseline entry, skipping")
            continue
        for speed_key in _SPEEDUPS:
            b_speed = bc.get(speed_key)
            f_speed = fc.get(speed_key)
            if b_speed and f_speed:
                ratio = f_speed / b_speed
                if tracked:
                    print(f"trk  {key}: {speed_key} {b_speed:.2f}x -> "
                          f"{f_speed:.2f}x ({ratio:.2f} of baseline, "
                          f"ratio-tracked only)")
                    continue
                tag = "ok  " if ratio >= 1.0 - args.threshold else "FAIL"
                print(f"{tag} {key}: {speed_key} {b_speed:.2f}x -> "
                      f"{f_speed:.2f}x ({ratio:.2f} of baseline)")
                if ratio < 1.0 - args.threshold:
                    failures.append(
                        f"{key}: {speed_key} regressed to "
                        f"{ratio:.2f} of baseline (threshold "
                        f"{1.0 - args.threshold:.2f})")
        if args.absolute and not tracked:
            ratio = fc["mean_tick_ms"] / bc["mean_tick_ms"]
            tag = "ok  " if ratio <= 1.0 + args.threshold else "FAIL"
            print(f"{tag} {key}: mean_tick_ms {bc['mean_tick_ms']:.2f} "
                  f"-> {fc['mean_tick_ms']:.2f} ({ratio:.2f}x)")
            if ratio > 1.0 + args.threshold:
                failures.append(
                    f"{key}: mean_tick_ms regressed {ratio:.2f}x "
                    f"(threshold {1.0 + args.threshold:.2f}x)")
    head = fresh_blob.get("headline")
    if head:
        speed = head.get("speedup_cached_vs_uncached", 0.0)
        tag = "ok  " if speed >= HEADLINE_FLOOR else "FAIL"
        print(f"{tag} headline J={head.get('J')} W={head.get('W')}: "
              f"cached {speed:.2f}x uncached "
              f"(floor {HEADLINE_FLOOR:.0f}x)")
        if speed < HEADLINE_FLOOR:
            failures.append(
                f"headline cached-vs-uncached speedup {speed:.2f}x "
                f"below the {HEADLINE_FLOOR:.0f}x acceptance floor")
    rhead = fresh_blob.get("regions_headline")
    if rhead:
        speed = rhead.get("speedup_hier_vs_flat", 0.0)
        tag = "ok  " if speed >= REGIONS_FLOOR else "FAIL"
        print(f"{tag} regions_headline J={rhead.get('J')} "
              f"W={rhead.get('W')} k={rhead.get('regions')}: "
              f"hierarchical {speed:.2f}x flat "
              f"(floor {REGIONS_FLOOR:.0f}x)")
        if speed < REGIONS_FLOOR:
            failures.append(
                f"regions_headline hier-vs-flat speedup {speed:.2f}x "
                f"below the {REGIONS_FLOOR:.0f}x acceptance floor")
    dhead = fresh_blob.get("drift_headline")
    if dhead:
        ratio = dhead.get("violation_ratio_stale_vs_online", 0.0)
        tag = "ok  " if ratio >= DRIFT_FLOOR else "FAIL"
        print(f"{tag} drift_headline J={dhead.get('J')} "
              f"W={dhead.get('W')} factor={dhead.get('factor')}: "
              f"stale {ratio:.2f}x online violations "
              f"(floor {DRIFT_FLOOR:.0f}x)")
        if ratio < DRIFT_FLOOR:
            failures.append(
                f"drift_headline stale-vs-online violation ratio "
                f"{ratio:.2f}x below the {DRIFT_FLOOR:.0f}x "
                f"acceptance floor")
    ehead = fresh_blob.get("energy_headline")
    if ehead:
        # deterministic like the drift ratio: fixed seeds, no timing.
        # acceptance is energy OR carbon >= 20% cut, at <= +10% extra
        # QoS violations vs the energy-blind baseline.
        cut = max(ehead.get("energy_reduction", 0.0),
                  ehead.get("carbon_reduction", 0.0))
        over = ehead.get("violation_overhead", 0.0)
        ok = cut >= ENERGY_FLOOR and over <= ENERGY_VIOL_CEIL
        tag = "ok  " if ok else "FAIL"
        print(f"{tag} energy_headline J={ehead.get('J')} "
              f"W={ehead.get('W')}: best aware-vs-blind cut "
              f"{cut:.3f} (floor {ENERGY_FLOOR:.2f}), violation "
              f"overhead {over:+.3f} (ceiling "
              f"+{ENERGY_VIOL_CEIL:.2f})")
        if cut < ENERGY_FLOOR:
            failures.append(
                f"energy_headline aware-vs-blind cut {cut:.3f} below "
                f"the {ENERGY_FLOOR:.2f} acceptance floor")
        if over > ENERGY_VIOL_CEIL:
            failures.append(
                f"energy_headline violation overhead {over:+.3f} above "
                f"the +{ENERGY_VIOL_CEIL:.2f} ceiling")
    ohead = fresh_blob.get("overload_headline")
    if ohead:
        # deterministic like the drift ratio: fixed seeds and a fixed
        # fault timeline — goodput counts, not wall-clock.  acceptance
        # is controlled >= 1.5x uncontrolled goodput at a p99 queue
        # depth under the recorded bound.
        ratio = ohead.get("goodput_ratio_controlled_vs_uncontrolled", 0.0)
        p99 = ohead.get("queue_depth_p99_controlled", float("inf"))
        bound = ohead.get("queue_depth_bound", 0.0)
        ok = ratio >= OVERLOAD_FLOOR and p99 <= bound
        tag = "ok  " if ok else "FAIL"
        print(f"{tag} overload_headline J={ohead.get('J')} "
              f"W={ohead.get('W')}: controlled {ratio:.2f}x "
              f"uncontrolled goodput (floor {OVERLOAD_FLOOR:.1f}x), "
              f"depth p99 {p99:.0f} (bound {bound:.0f})")
        if ratio < OVERLOAD_FLOOR:
            failures.append(
                f"overload_headline controlled-vs-uncontrolled goodput "
                f"{ratio:.2f}x below the {OVERLOAD_FLOOR:.1f}x "
                f"acceptance floor")
        if p99 > bound:
            failures.append(
                f"overload_headline controlled p99 queue depth "
                f"{p99:.0f} above the {bound:.0f} bound")
    if failures:
        print("\nperf regression gate FAILED:")
        for f_ in failures:
            print(f"  - {f_}")
        return 1
    print("perf regression gate passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
