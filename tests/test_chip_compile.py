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


def _materialized(hlo: str) -> dict:
    """Instruction name -> (dtype, dims) for every array-valued
    instruction outside a fused computation: the arrays the program
    writes to memory, not the values a fusion keeps to itself."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo))
    out, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]", line)
        if m and comp not in fused:
            out[m.group(1)] = (m.group(2), tuple(
                int(d) for d in m.group(3).split(",") if d))
    return out


def _attention_products(hlo: str) -> list:
    """(lhs dtype, rhs dtype, operand precision) of each product that
    decode attention's scores or values make, from the optimized HLO."""
    dtypes = dict(re.findall(r"%([\w.\-]+) = (\w+)\[", hlo))
    out = []
    for line in hlo.splitlines():
        m = re.search(r" (?:convolution|dot)\(%([\w.\-]+), %([\w.\-]+)\)",
                      line)
        if m and re.search(r"bkg[ds],bskd->bkg[sd]/dot_general", line):
            prec = re.search(r"operand_precision=\{([^}]*)\}", line)
            out.append((dtypes[m.group(1)], dtypes[m.group(2)],
                        prec.group(1) if prec else "default,default"))
    return out


def test_qwen3_4b_decode_attention_reads_the_cache_in_place(
        one_chip, no_compile_cache):
    """The decode step at the ``qwen3-4b.decode`` cell's shape (4 rows x
    2,560 positions) reads K and V where the stacked cache holds them:
    no array outside a fusion holds one layer's K or V, no array
    anywhere holds a chunk widened to the 32 query heads, and no array
    holds a cache chunk in f32.  Both products multiply bf16 operands,
    which the MXU takes whole, or run at HIGHEST precision: neither
    rounds an f32 operand to bf16."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.models.registry import build_model

    cfg = get_config("qwen3-4b")
    B, L, K, hd, C = 4, 2560, cfg.n_kv_heads, cfg.head_dim, cfg.attn_chunk
    G = cfg.n_heads // K
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    params = place(jax.eval_shape(model.init_params,
                                  jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(lambda: model.init_cache(B, L)))
    step = place({"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                  "pos": jax.ShapeDtypeStruct((), jnp.int32)})
    hlo = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, caches, step).compile().as_text()
    arrays = _materialized(hlo)
    shapes = _hlo_shapes(hlo)
    assert (cfg.n_layers, B, L, K, hd) in shapes.values()

    layer = {(1, B, L, K, hd), (B, L, K, hd)}
    assert [n for n, (_, s) in arrays.items() if s in layer] == []
    widened = {(B, C, K, G, hd), (B, C, K * G, hd)}
    assert [n for n, s in shapes.items() if s in widened] == []
    chunk = {(B, C, K, hd), (1, B, C, K, hd)}
    f32_chunks = re.findall(
        r"%([\w.\-]+) = f32\[([\d,]*)\]", hlo)
    assert [n for n, d in f32_chunks
            if tuple(int(x) for x in d.split(",") if x) in chunk] == []
    products = _attention_products(hlo)
    assert len(products) == 2
    assert [p for p in products if p[:2] != ("bf16", "bf16")
            and p[2] != "highest,highest"] == []


@pytest.fixture(scope="module")
def deepseek_share(one_chip):
    """The model and parameter shapes of one chip's share of DeepSeek-V2
    as the benchmark's ``deepseek-v2.decode`` cell runs it: the first 7
    layers, experts 0-15 of each expert layer, prefill by blocks of 2,048
    tokens."""
    import dataclasses

    import jax

    from repro.configs.registry import get_config
    from repro.models.registry import build_model

    cfg = get_config("deepseek-v2-236b")
    cfg = dataclasses.replace(cfg, n_layers=7, prefill_tokens=2048,
                              moe=dataclasses.replace(cfg.moe, n_held=16))
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    params = place(jax.eval_shape(model.init_params,
                                  jax.random.PRNGKey(0)))
    return cfg, model, params, place


def _need(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_deepseek_v2_decode_attends_in_the_latent_space(
        deepseek_share, no_compile_cache):
    """The 64-row decode step over 2,560 positions: no array holds the
    latent (576 wide) or a key (192 wide) for each of the 128 query heads
    at many positions (a chunk of 512 or the whole cache), as repeating
    the latent per head or re-expanding keys would; and the step fits
    one chip."""
    import jax
    import jax.numpy as jnp

    cfg, model, params, place = deepseek_share
    B, L = 64, 2560
    caches = place(jax.eval_shape(lambda: model.init_cache(B, L)))
    step = place({"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                  "pos": jax.ShapeDtypeStruct((), jnp.int32)})
    compiled = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, caches, step).compile()
    shapes = set(_hlo_shapes(compiled.as_text()).values())
    seq = {cfg.attn_chunk, L}
    per_head = [s for s in shapes if len(s) >= 3 and s[0] == B
                and 128 in s[1:] and {576, 192} & set(s[1:])
                and seq & set(s[1:])]
    assert per_head == [], sorted(per_head)[:5]
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"deepseek-v2 decode {B}x{L}: temp {temp} B")
    assert temp < 2e9
    assert _need(compiled) < V5E_HBM_BYTES


def test_deepseek_v2_prefill_by_blocks_fits(deepseek_share,
                                            no_compile_cache):
    """A 64 x 2,048 prompt prefills as one program by blocks of 2,048
    tokens: its activations stay near 2 GB beside the 9.68 GB of
    weights and the 1.07 GB of latent cache it writes."""
    import jax
    import jax.numpy as jnp

    cfg, model, params, place = deepseek_share
    tok = place(jax.ShapeDtypeStruct((64, 2048), jnp.int32))
    compiled = jax.jit(model.prefill).lower(params, {"tokens": tok}).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"deepseek-v2 prefill 64x2048: temp {temp} B")
    assert temp < 3e9
    assert _need(compiled) < V5E_HBM_BYTES - 2 * 1.33e9


# decode temp of the share's 64 x 2,560 step while each expert layer's
# held experts were copied out of the layer stack for the grouped products
DEEPSEEK_DECODE_TEMP_WITH_COPIES = 1_113_846_272


def test_deepseek_v2_programs_read_the_held_experts_in_place(
        deepseek_share, no_compile_cache):
    """The 64 x 2,560 decode step and the 64 x 2,048 prefill by blocks
    hand the grouped products each expert layer's held experts where the
    layer stack keeps them: no array of one layer's held experts' shape
    ([16, 5120, 1536] or [16, 1536, 5120]) appears in either optimized
    program, and the decode step needs no more temporaries than with the
    per-layer copies."""
    import jax
    import jax.numpy as jnp

    cfg, model, params, place = deepseek_share
    held, D, F = cfg.moe.held[1], cfg.d_model, cfg.moe.d_ff_expert
    one_layer = [f"[{held},{D},{F}]", f"[{held},{F},{D}]"]
    stack = params["groups"][1]["moe"]["wi"].shape
    assert stack == (cfg.n_layers - cfg.n_dense_layers, held, D, F)

    B, L = 64, 2560
    caches = place(jax.eval_shape(lambda: model.init_cache(B, L)))
    step = place({"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                  "pos": jax.ShapeDtypeStruct((), jnp.int32)})
    decode = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, caches, step).compile()
    tok = place(jax.ShapeDtypeStruct((64, 2048), jnp.int32))
    prefill = jax.jit(model.prefill).lower(params, {"tokens": tok}).compile()
    for what, compiled in (("decode", decode), ("prefill", prefill)):
        hlo = compiled.as_text()
        assert "ragged" in hlo, what
        found = [s for s in one_layer if s in hlo]
        assert found == [], (what, found)
    temp = decode.memory_analysis().temp_size_in_bytes
    print(f"deepseek-v2 decode {B}x{L}: temp {temp} B (with the copies "
          f"{DEEPSEEK_DECODE_TEMP_WITH_COPIES} B); prefill 64x2048: temp "
          f"{prefill.memory_analysis().temp_size_in_bytes} B")
    assert temp <= DEEPSEEK_DECODE_TEMP_WITH_COPIES


# temp bytes of qwen3-4b's programs at the benchmark cells' shapes: the
# prefill programs as compiled before the MLA and expert layers changed
# the shared attention and decoder code, the decode programs since
# decode attention reads the cache where it lies (581,120 and 354,816
# before); a change to shared code must not move them unnoticed
QWEN3_4B_TEMP = {("prefill", 4, 2048): 1_024_669_184,
                 ("prefill", 1, 4096): 315_315_712,
                 ("decode", 4, 2560): 483_840,
                 ("decode", 1, 4224): 387_072}


@pytest.mark.parametrize("kind,B,L", sorted(QWEN3_4B_TEMP),
                         ids=lambda v: str(v))
def test_qwen3_4b_programs_keep_their_temp_bytes(one_chip, no_compile_cache,
                                                 kind, B, L):
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.models.registry import build_model

    model = build_model(get_config("qwen3-4b"))
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    params = place(jax.eval_shape(model.init_params,
                                  jax.random.PRNGKey(0)))
    if kind == "prefill":
        tok = place(jax.ShapeDtypeStruct((B, L), jnp.int32))
        compiled = jax.jit(model.prefill).lower(
            params, {"tokens": tok}).compile()
    else:
        caches = place(jax.eval_shape(lambda: model.init_cache(B, L)))
        step = place({"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                      "pos": jax.ShapeDtypeStruct((), jnp.int32)})
        compiled = jax.jit(model.decode, donate_argnums=(1,)).lower(
            params, caches, step).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == \
        QWEN3_4B_TEMP[(kind, B, L)]
