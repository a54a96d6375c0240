#!/usr/bin/env python3
"""Run the system's main path once on one TPU chip, at real size.

    python3 chip_smoke.py          # from the root of a checkout

All phases run in this one process, in order, and any failure ends the
script with a non-zero exit code:

1. The scheduling tick at fleet backlog: the standing-backlog churn loop
   of ``bench_sched_overhead`` at J=10,000 queued jobs x W=64 pools
   (``synth_fleet(8, 28, 28)``), the device-resident policy (compiled
   ``scheduler_tick``) against cached numpy ``SynergAI()`` on the same
   churn stream.  Per-tick assignments must be equal.
2. The simulator end to end: ``Simulator(...).run(jobs)`` over a batched,
   streaming MMPP trace of 2,000 jobs at utilization 3 on the same fleet;
   the device-resident policy's ``JobResult`` stream must equal numpy's.
3. The engine: ``repro.launch.serve`` brings up qwen3-4b at its published
   widths in bf16 with seeded weights and answers requests (prefill
   8 x 512, then 16 decode steps); logits must be finite, token shapes
   right, and one decode step must match a prefill over the extended
   prompt.

Lines before the last are set-up information.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Without a TPU the script prints that it found none and exits 2.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# max |decode-step logits - prefill logits| over max |prefill logits|:
# float32 matches tests/test_models.py; bf16 keeps 8 mantissa bits
# (eps = 2^-8), and the two programs round differently through every
# layer, so the bound is about 13 eps
LOGIT_TOL = {"float32": 2e-3, "bfloat16": 5e-2}


def _check_compiled(pol, on_chip: bool):
    if on_chip and pol.cache.interpret is not False:
        raise RuntimeError("device-resident policy would run the Pallas "
                           "interpreter, not the compiled tick")


def phase_tick(cd, J=10_000, pools=(8, 28, 28), iters=40, churn=64,
               on_chip=True, emit=print):
    """Phase 1: device-resident tick vs cached numpy, tick by tick."""
    from benchmarks.scheduler_experiments import backlog_churn
    from repro.core.scheduler import SynergAI
    from repro.core.workers import synth_fleet

    want, _, _ = backlog_churn(cd, SynergAI, J, pools, iters=iters,
                               churn=churn)
    got, _, pol = backlog_churn(cd, lambda: SynergAI(device=True), J,
                                pools, iters=iters, churn=churn)
    _check_compiled(pol, on_chip)
    for i, (g, w) in enumerate(zip(got, want)):
        if g != w:
            raise AssertionError(f"phase 1: tick {i} assignments differ "
                                 f"from numpy: {g[:4]} != {w[:4]}")
    if len(got) != iters or len(want) != iters:
        raise AssertionError("phase 1: tick count differs")
    dc = pol.cache
    placed = sum(len(a) for a in got)
    emit(f"phase 1 ok: J={J} W={len(synth_fleet(*pools))} "
         f"ticks={dc.ticks} placed={placed} assignments equal numpy; "
         f"rows_uploaded={dc.rows_uploaded} "
         f"bytes_to_device={dc.bytes_to_device} "
         f"pool_shape={tuple(dc._dt.shape)} interpret={dc.interpret}")
    return got


def _result_key(results):
    return [(r.job.id, r.worker, r.config, r.start, r.end, r.waiting,
             r.exec_s, r.e2e, r.violated, r.excess, r.ttft, r.tpot)
            for r in results]


def phase_sim(cd, n_jobs=2_000, pools=(8, 28, 28), utilization=3.0,
              on_chip=True, emit=print):
    """Phase 2: a batched, streaming simulator run, device vs numpy."""
    from repro.core.scheduler import SynergAI
    from repro.core.simulator import Simulator
    from repro.core.workers import synth_fleet
    from repro.core.workload import scenario

    fleet = synth_fleet(*pools)

    def run(pol):
        jobs = scenario(cd, "mmpp", n_jobs=n_jobs, fleet=fleet, seed=11,
                        utilization=utilization, serving="batched",
                        streaming=(2.0, 2.5))
        return _result_key(Simulator(cd, pol, fleet=fleet, seed=11,
                                     serving="batched").run(jobs))

    want = run(SynergAI())
    pol = SynergAI(device=True)
    got = run(pol)
    _check_compiled(pol, on_chip)
    if len(got) != len(want) or len(got) != n_jobs:
        raise AssertionError(f"phase 2: {len(got)} results on the device "
                             f"path, {len(want)} on numpy, {n_jobs} jobs")
    for g, w in zip(got, want):
        if g != w:
            raise AssertionError(f"phase 2: job results differ: {g} != {w}")
    emit(f"phase 2 ok: {n_jobs} jobs on {len(fleet)} pools, "
         f"device ticks={pol.cache.ticks}, "
         f"violated={sum(r[8] for r in got)}; JobResult stream equals "
         f"numpy")
    return got


def phase_engine(cd, arch="qwen3-4b", reduced=False, requests=2, gen=17,
                 batch=None, prompt_len=None, on_chip=True, emit=print):
    """Phase 3: the serve launcher's path, then decode-vs-prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import build_engine, engine_spec, serve

    spec = engine_spec(arch)
    S = prompt_len or spec.prefill_len
    eng = build_engine(arch, reduced=reduced, seed=0)
    cfg = eng.model.cfg
    leaves = jax.tree.leaves(eng.params)
    n_params = sum(int(x.size) for x in leaves)
    dtypes = sorted({str(x.dtype) for x in leaves})
    if on_chip and {d.platform for x in leaves for d in x.devices()} \
            != {"tpu"}:
        raise RuntimeError("phase 3: parameters are not on the TPU")
    emit(f"phase 3: {arch} layers={cfg.n_layers} d_model={cfg.d_model} "
         f"vocab={cfg.vocab} params={n_params} dtypes={dtypes}")
    served = serve(eng, arch, requests=requests, gen=gen, batch=batch,
                   prompt_len=S, cd=cd, emit=emit)
    B = batch or spec.microbatch
    for s in served:
        t = np.asarray(s.tokens)
        if t.shape != (B, gen) or t.min() < 0 or t.max() >= cfg.vocab:
            raise AssertionError(f"phase 3: request {s.rid} tokens "
                                 f"shape {t.shape}, range "
                                 f"[{t.min()}, {t.max()}]")
    # one decode step after the prompt == a prefill over prompt + token
    toks = served[0].batch["tokens"]
    nxt = served[0].tokens[:, 0]
    logits_p, caches = eng.prefill({"tokens": toks})
    logits_d, _ = eng.decode_step(caches, nxt, S)
    logits_f, _ = eng.prefill(
        {"tokens": jnp.concatenate([toks, nxt[:, None]], axis=1)})
    ld, lf = (np.asarray(x, np.float32) for x in (logits_d, logits_f))
    for name, x in (("prefill", np.asarray(logits_p, np.float32)),
                    ("decode", ld), ("extended", lf)):
        if x.shape != (B, cfg.vocab) or not np.isfinite(x).all():
            raise AssertionError(f"phase 3: {name} logits shape "
                                 f"{x.shape} or not finite")
    rel = float(np.max(np.abs(ld - lf)) / (np.max(np.abs(lf)) + 1e-9))
    tol = LOGIT_TOL[cfg.dtype]
    if not rel < tol:
        raise AssertionError(f"phase 3: decode step vs extended prefill "
                             f"rel={rel:.3e} >= {tol} ({cfg.dtype})")
    emit(f"phase 3 ok: {len(served)} requests of {B}x{S}, {gen} tokens "
         f"each ({gen - 1} decode steps after the prefill's token); "
         f"decode vs extended prefill rel={rel:.3e} (< {tol}, "
         f"{cfg.dtype})")
    return rel


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}")
    print(f"compile cache: {enable_compile_cache()}")
    from repro.core.offline import characterize

    cd = characterize()
    for name, phase in (("1", phase_tick), ("2", phase_sim),
                        ("3", phase_engine)):
        t0 = time.perf_counter()
        phase(cd)
        print(f"phase {name} set-up+run wall {time.perf_counter() - t0:.1f}"
              f" s (compilation included)")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
