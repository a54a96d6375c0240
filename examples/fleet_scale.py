"""Fleet-scale scenario demo: a 64-pool synthetic cluster serving bursty,
diurnal and multi-tenant traffic with worker failures, scheduled by
SynergAI on the event-heap simulator — optionally with each tick run by
the device-resident Pallas kernel (--device), optionally served through
the continuous-batching serving bridge (--serving batched; see
docs/serving_bridge.md).

    PYTHONPATH=src python examples/fleet_scale.py [--jobs 2000] [--device]
    PYTHONPATH=src python examples/fleet_scale.py --serving batched
"""

import argparse
import time

from repro.core.metrics import summarize
from repro.core.offline import characterize
from repro.core.scheduler import SynergAI
from repro.core.simulator import Simulator
from repro.core.workers import synth_fleet
from repro.core.workload import (SCENARIOS, index_of_dispersion, scenario,
                                 synth_failures)

parser = argparse.ArgumentParser()
parser.add_argument("--jobs", type=int, default=2000)
parser.add_argument("--pools", type=int, nargs=3, default=(8, 28, 28),
                    metavar=("CLOUD", "EDGE_LG", "EDGE_SM"))
parser.add_argument("--serving", choices=("job", "batched"),
                    default="job",
                    help="service model: exclusive job-level occupancy "
                         "(paper §5.1) or the continuous-batching serving "
                         "bridge (token-level requests, KV-bounded "
                         "batches)")
parser.add_argument("--max-batch", type=int, default=8,
                    help="continuous-batch slot budget per worker "
                         "(batched serving only)")
parser.add_argument("--device", action="store_true",
                    help="run each scheduling tick as one device "
                         "dispatch (SynergAI(device=True)); off a TPU the "
                         "Pallas interpreter emulates it op-by-op, so "
                         "keep --jobs <= ~100 there")
args = parser.parse_args()

cd = characterize()
fleet = synth_fleet(*args.pools)
print(f"fleet: {len(fleet)} pools "
      f"(cloud={args.pools[0]}, edge-large={args.pools[1]}, "
      f"edge-small={args.pools[2]})")

for kind in SCENARIOS:
    jobs = scenario(cd, kind, n_jobs=args.jobs, fleet=fleet,
                    seed=0, serving=args.serving)
    span = jobs[-1].arrival
    disp = index_of_dispersion([j.arrival for j in jobs], 60.0)
    failures = synth_failures(fleet, span, mtbf_s=2 * span, mttr_s=120.0,
                              seed=0)
    t0 = time.perf_counter()
    res = Simulator(cd, SynergAI(device=args.device), fleet=fleet,
                    failures=failures, seed=0, serving=args.serving,
                    max_batch=args.max_batch).run(jobs)
    dt = time.perf_counter() - t0
    s = summarize(res)
    print(f"{kind:13s} span={span:7.0f}s dispersion={disp:6.1f} "
          f"failures={len(failures):3d} violations={s['violations']:5d} "
          f"wait={s['waiting_avg_s']:7.1f}s p99={s['e2e_p99_s']:7.1f}s "
          f"wall={dt:5.2f}s")
