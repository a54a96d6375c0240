"""Benchmark harness — one section per paper table/figure.

Prints ``name,...`` CSV lines.  Sections:
  characterization     (Fig. 1, Key Outcome 1)
  vertical_scaling     (Fig. 2, Key Outcome 2)
  operating_modes      (Fig. 3-5 + Table 2, Key Outcomes 3/4)
  scheduler            (Fig. 7/8/9 + Fig. 10 SLO-MAEL comparison)
  overhead             (Fig. 11)
  energy               (Fig. 12)
  kernel               (Pallas kernel microbenches)
  roofline             (dry-run derived; §Roofline in EXPERIMENTS.md)

Fleet-scale entry points (not run here; each has its own CLI):
  benchmarks/scheduler_experiments.py   10k-job x 64-pool scenarios under
      every policy, old-vs-new simulator wall clock, numpy-vs-Pallas
      scoring, the job-level vs batched serving-bridge comparison, and
      the trace-driven bench_traces (replay / drift / correlated-region
      outage) (--jobs/--pools/--kind, --skip-* flags)
  examples/fleet_scale.py               64-pool demo over every
      scenario preset (--serving {job,batched} selects the service
      model; scenario(..., serving="batched") token-level requests)
  examples/serve_bridge.py              serving-bridge demo with
      per-pool batch stats (docs/serving_bridge.md)
  examples/replay_trace.py              trace export/replay bit-for-bit,
      engine-popularity drift, correlated regional outages
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter).parse_args()
    t0 = time.time()
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.core.offline import characterize
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (characterization, energy, kernels_bench,
                            operating_modes, overhead, roofline,
                            scheduler_experiments, vertical_scaling)

    cd = characterize()
    print("# characterization (Fig. 1)")
    characterization.run(cd)
    print("# vertical scaling (Fig. 2)")
    vertical_scaling.run()
    print("# operating modes (Fig. 3-5)")
    operating_modes.run()
    print("# scheduler experiments (Fig. 7-10)")
    scheduler_experiments.run(cd)
    print("# scheduling overhead (Fig. 11)")
    overhead.run(cd)
    print("# energy (Fig. 12)")
    energy.run(cd)
    print("# kernel microbenches")
    kernels_bench.run()
    print("# roofline (from dry-run artifacts, single-pod)")
    if os.path.isdir("artifacts/dryrun"):
        roofline.run()
    else:
        print("roofline,skipped=no artifacts/dryrun "
              "(run python -m repro.launch.dryrun --all first)")
    print(f"# total bench time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
