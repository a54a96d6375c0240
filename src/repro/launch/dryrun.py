import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x input shape) cell, AOT-lower and compile the cell's
step function (train_step / prefill_step / serve_step) against the production
mesh — 16x16 single-pod and 2x16x16 multi-pod — with ShapeDtypeStruct inputs
(no allocation), then record:

  - memory_analysis()            (proves the per-device program fits)
  - cost_analysis()              (per-device HLO FLOPs / bytes)
  - collective bytes             (parsed from the optimized HLO text)

into a JSON artifact per cell under artifacts/dryrun/.  benchmarks/roofline.py
turns these into the EXPERIMENTS.md roofline table.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.registry import SHAPES, all_cells, cell_applicable, get_config
from repro.distributed import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import make_train_step

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op in optimized HLO."""
    per_op = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, rhs = stripped.split("=", 1)
        rhs = rhs.strip()
        for coll in COLLECTIVES:
            # match ` = <type> all-reduce(` and `-start(` variants
            m = re.match(rf"^(\(?[\w\[\],\s{{}}:#*]*?)\s{coll}(-start)?\(",
                         rhs)
            if not m:
                continue
            tybytes = 0
            for dt, dims in _SHAPE_RE.findall(m.group(1)):
                if dt not in DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                tybytes += n * DTYPE_BYTES[dt]
            per_op[coll] += tybytes
            counts[coll] += 1
            break
    return per_op, counts


def build_cell_fn(arch: str, shape_name: str, mesh, extra_tags=()):
    """Returns (fn, example_args, in_shardings, donate) for one cell."""
    cfg = get_config(arch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    batch_specs = model.input_specs(shape)
    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(model.init_params, key)
    rules = sh.TRAIN_RULES if shape.kind == "train" else sh.PARAM_RULES
    p_spec = sh.param_pspecs(params_shapes, mesh, rules)
    b_spec = sh.batch_pspecs(batch_specs, mesh)

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(init_opt_state, params_shapes)
        state_shapes = {"params": params_shapes, "opt": opt_shapes}
        state_spec = {"params": p_spec,
                      "opt": {"m": sh.opt_pspecs(params_shapes, mesh),
                              "v": sh.opt_pspecs(params_shapes, mesh),
                              "step": jax.sharding.PartitionSpec()}}
        grad_shardings = sh.to_shardings(
            sh.opt_pspecs(params_shapes, mesh), mesh)
        # v5e 16 GB/chip: the largest models microbatch the global batch
        accum = {"deepseek-v2-236b": 8, "phi3.5-moe-42b-a6.6b": 2,
                 "llama-3.2-vision-11b": 2}.get(arch, 1)
        step = make_train_step(model, AdamWConfig(),
                               grad_shardings=grad_shardings,
                               accum_steps=accum)
        args = (state_shapes, batch_specs)
        in_specs = (state_spec, b_spec)
        donate = (0,)
        return step, args, in_specs, donate

    if shape.kind == "prefill":
        def step(params, batch):
            return model.prefill(params, batch)
        args = (params_shapes, batch_specs)
        in_specs = (p_spec, b_spec)
        return step, args, in_specs, ()

    # decode: one new token against a seq_len-deep cache
    cache_shapes = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    c_spec = sh.cache_pspecs(cache_shapes, mesh)
    def step(params, caches, batch):
        return model.decode(params, caches, batch)
    args = (params_shapes, cache_shapes, batch_specs)
    in_specs = (p_spec, c_spec, b_spec)
    return step, args, in_specs, (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             tag="") -> dict:
    mesh_name = "multi" if multi_pod else "single"
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "tag": tag, "status": "skip", "skip_reason": why}
    if ok:
        t0 = time.time()
        try:
            mesh = make_production_mesh(multi_pod=multi_pod)
            fn, args, in_specs, donate = build_cell_fn(
                arch, shape_name, mesh)
            sh.set_active_mesh(mesh)
            try:
                with mesh:
                    jitted = jax.jit(
                        fn,
                        in_shardings=sh.to_shardings(in_specs, mesh),
                        donate_argnums=donate)
                    lowered = jitted.lower(*args)
                    t_lower = time.time() - t0
                    compiled = lowered.compile()
                    t_compile = time.time() - t0 - t_lower
                    # loop-aware jaxpr FLOP/byte counts of the program as
                    # compiled on the mesh (cost_analysis counts scan
                    # bodies once; see launch/flopcount.py)
                    from repro.launch import flopcount
                    jx = flopcount.analyze(fn, *args)
            finally:
                sh.set_active_mesh(None)
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            from repro.launch.hloparse import collective_bytes_loop_aware
            coll, counts = collective_bytes_loop_aware(hlo)
            coll_flat, _ = collective_bytes(hlo)  # unscaled, for reference
            record.update(
                status="ok",
                n_devices=mesh.devices.size,
                lower_s=round(t_lower, 2),
                compile_s=round(t_compile, 2),
                memory={
                    "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                    "output_bytes": getattr(ma, "output_size_in_bytes", None),
                    "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                    "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
                    "code_bytes": getattr(ma, "generated_code_size_in_bytes",
                                          None),
                },
                cost={k: v for k, v in ca.items()
                      if "flops" in k or "bytes" in k or "utilization" in k},
                jaxpr_flops_global=jx["flops_global"],
                jaxpr_bytes_global=jx["bytes_global"],
                collective_bytes_per_device=coll,
                collective_bytes_unscaled=coll_flat,
                collective_counts=counts,
                hlo_bytes=len(hlo),
            )
            print(f"[ok] {arch} x {shape_name} x {mesh_name}"
                  f"{(' (' + tag + ')') if tag else ''}: "
                  f"compile {t_compile:.1f}s, "
                  f"flops/dev {ca.get('flops', 0):.3g}, "
                  f"coll/dev {sum(coll.values()):.3g}B")
            # the assignment's required outputs:
            print("  memory_analysis:", record["memory"])
        except Exception as e:  # noqa: BLE001 — record and continue
            record.update(status="error", error=f"{type(e).__name__}: {e}",
                          traceback=traceback.format_exc()[-2000:])
            print(f"[ERROR] {arch} x {shape_name} x {mesh_name}: {e}")
    else:
        print(f"[skip] {arch} x {shape_name}: {why}")

    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_name}{('__' + tag) if tag else ''}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="enable the beyond-paper §Perf optimizations "
                         "(shard_map flash-decode, tuned attn chunks)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.opt:
        import dataclasses as _dc

        from repro.configs import registry as _creg
        from repro.models import layers as _layers
        _layers.SHARDED_DECODE_ATTN = True
        _creg.ARCHS["gemma-2b"] = _dc.replace(_creg.ARCHS["gemma-2b"],
                                              attn_chunk=4096)
        if not args.tag:
            args.tag = "opt"

    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    cells = []
    if args.all:
        for arch, cfg, shape, ok, why in all_cells():
            cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape_name in cells:
        for mesh_name in meshes:
            fname = (f"{arch}__{shape_name}__{mesh_name}"
                     f"{('__' + args.tag) if args.tag else ''}.json")
            path = os.path.join(args.out, fname)
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") in ("ok", "skip"):
                        continue
            rec = run_cell(arch, shape_name, mesh_name == "multi", args.out,
                           tag=args.tag)
            if rec["status"] == "error":
                failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
