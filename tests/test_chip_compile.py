"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(block shapes off the tiling, dtypes Mosaic cannot store, programs that
do not fit the device's memory).  Interpret-mode tests cannot see any of
that.  Shapes are the ``chip_smoke.py`` ones: the scheduling tick at
J=10,000 x W=64 padded to (10,240 x 128) with the 32,768-row pool the
device cache grows to there, and qwen3-4b decode at its published
widths, also at the benchmark cells' cache shapes.

Every compile for the described chip happens in this file, in the
test's own process: the topology is described in a module fixture, never
at import, so every xdist worker collects the same tests and only the
one running this file loads the TPU library.
"""

import os
import re

import pytest

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep it out of the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("use_energy,K", [(False, 1), (True, 8)],
                         ids=["job", "batched-energy"])
def test_scheduler_tick_compiles_for_v5e(one_chip, no_compile_cache,
                                         use_energy, K):
    import jax
    import jax.numpy as jnp

    from repro.kernels.scheduler_score import scheduler_tick

    Jp, Wp, cap = 10_240, 128, 32_768
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                               sharding=one_chip)
    f32, i32 = jnp.float32, jnp.int32
    pool = S((cap, Wp), f32)
    args = (pool, pool, pool, S((cap if use_energy else 1, Wp), f32),
            S((Jp,), i32), *[S((Jp,), f32)] * 4, *[S((Jp,), i32)] * 4,
            S((K, Wp), jnp.bool_), *[S((Wp,), f32)] * 3,
            S((Wp,), jnp.bool_))
    compiled = scheduler_tick.lower(*args, use_energy=use_energy, bj=128,
                                    interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20


def test_qwen3_4b_decode_compiles_and_fits_v5e(one_chip, no_compile_cache):
    import jax
    import jax.numpy as jnp

    from repro.core.engines import default_engines
    from repro.models.registry import build_model

    spec = default_engines()["qwen3-4b/bf16"]
    model = build_model(spec.cfg)
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    params = place(jax.eval_shape(model.init_params,
                                  jax.random.PRNGKey(0)))
    B, L = spec.microbatch, spec.prefill_len + spec.decode_len
    caches = place(jax.eval_shape(lambda: model.init_cache(B, L)))
    step = place({"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                  "pos": jax.ShapeDtypeStruct((), jnp.int32)})
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    compiled = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, caches, step).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 8e9      # the whole 4.4 B params
    assert need < V5E_HBM_BYTES, need


def _hlo_shapes(hlo: str) -> dict:
    """Instruction name -> result dims, for every array-valued line."""
    shapes = {}
    for m in re.finditer(r"%([\w.\-]+) = \w+\[([\d,]*)\]", hlo):
        shapes[m.group(1)] = tuple(int(d) for d in m.group(2).split(",") if d)
    return shapes


@pytest.mark.parametrize("B,L", [(4, 2560), (1, 4224)],
                         ids=["decode-cell", "prefill-cell"])
def test_qwen3_4b_decode_writes_cache_in_place(one_chip, no_compile_cache,
                                                B, L):
    """The donated decode step at the benchmark cells' shapes writes each
    layer's new position into the stacked cache where it lies: no
    instruction copies an array of the stack's shape, every
    dynamic-update-slice into the stack writes one position of one
    layer, and the step needs little memory beyond the cache."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.models.registry import build_model

    cfg = get_config("qwen3-4b")
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    params = place(jax.eval_shape(model.init_params,
                                  jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(lambda: model.init_cache(B, L)))
    step = place({"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                  "pos": jax.ShapeDtypeStruct((), jnp.int32)})
    compiled = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, caches, step).compile()
    hlo = compiled.as_text()
    shapes = _hlo_shapes(hlo)
    stack = (cfg.n_layers, B, L, cfg.n_kv_heads, cfg.head_dim)
    assert stack in {l.shape for l in jax.tree.leaves(caches)}

    copies = re.findall(r"%([\w.\-]+) = \w+\[[\d,]*\]\S* copy\(", hlo)
    assert [c for c in copies if shapes[c] == stack] == []
    writes = re.findall(
        r"%([\w.\-]+) = \w+\[[\d,]*\]\S* dynamic-update-slice\("
        r"%[\w.\-]+, %([\w.\-]+),", hlo)
    into_stack = [upd for out, upd in writes if shapes[out] == stack]
    assert into_stack, "no write into the stacked cache found"
    one = (1, B, 1, cfg.n_kv_heads, cfg.head_dim)
    assert all(shapes[u] == one for u in into_stack), \
        [shapes[u] for u in into_stack]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
