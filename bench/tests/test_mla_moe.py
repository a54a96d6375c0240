"""The latent-attention mixture-of-experts cell on the CPU at a small
size: the whole run through ``run_cell`` (its system, loop, reference
and counters), and the operation and byte counts of
``bench/flops_mla_moe.py``."""

import dataclasses

import pytest

from bench import flops_mla_moe as F
from bench import run as R
from bench.tests import tiny

CELL = "deepseek-v2.decode"
# the program's ``configs.base.reduced`` shape of deepseek-v2-236b, of
# which this chip holds experts 2 and 3 of 8
TINY_MLA_MOE = dict(layers=3, first_k_dense_replace=1, hidden_size=64,
                    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    intermediate_size=128, moe_intermediate_size=64,
                    n_routed_experts=2, first_held_expert=2,
                    published={"layers": 60, "n_routed_experts": 8},
                    num_experts_per_tok=2, n_group=4, topk_group=2,
                    vocab_size=256, torch_dtype="float32",
                    prefill_tokens=16)


def tiny_cell():
    bench = R.load_benchmark()
    c, cfg, traffic = R.resolve(bench, CELL)
    cfg = dict(cfg, **TINY_MLA_MOE)
    req = dict(tiny.tiny_requests(traffic["requests"]), batch=4)
    traffic = dict(traffic, requests=req,
                   max_len=traffic["max_len"] // tiny.SHRINK,
                   sample_rows=2, warm=1)
    return bench, c, cfg, traffic


def test_cell_runs_end_to_end_and_matches_the_reference():
    bench, c, cfg, traffic = tiny_cell()
    result, checks = R.run_cell(bench, c, cfg, traffic, tiny.SEED, 1.0,
                                False, emit=lambda m: None,
                                require_accelerator=False,
                                system_kw={"reduced": True},
                                limits=tiny.limits(CELL))
    assert result["correct"], checks
    assert checks["logit_gap_mean"]["value"] < 1e-4
    assert checks["off_best_share"]["value"] == 0
    assert set(result["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert result["attempted"] >= 2


def test_counters_carry_the_expert_load_of_the_window():
    bench, c, cfg, traffic = tiny_cell()
    parts = R.Parts(cfg, traffic)
    system, loop = parts.build(cfg, traffic, tiny.SEED, lambda m: None,
                               reduced=True)
    loop.warm()
    loop.window(0.5)
    got = loop.counters()
    load = got["expert_load"]
    # each token routes to 2 of 8 experts in each of the 2 expert layers;
    # about a quarter of those land on the 2 held
    routed = {"prefill": sum(b * p for b, p, _ in got["work"]),
              "decode": sum(b * (n - 1) for b, _, n in got["work"])}
    for ph, tokens in routed.items():
        assert 0 < load["held_assignments"][ph] <= tokens * 2 * 2
        assert 0 < load["experts_touched"][ph]
    assert "scopes" not in got          # no trace was taken


def test_every_seed_serves_the_ring_in_one_order_of_sizes():
    """``closed_rows`` serves the sizes of the ring that ``order_seed``
    draws, in its order, for any seed; the prompts' tokens are the
    seed's."""
    from bench.loops.closed_rows import in_fixed_order
    from bench.requests import lognormal

    _, _, traffic = R.resolve(R.load_benchmark(), CELL)
    req = traffic["requests"]
    want = [(r.prompt_len, r.out)
            for r in lognormal.make(req, traffic["order_seed"], 2)]
    rings = {}
    for seed in (tiny.SEED, 7):
        own = lognormal.make(req, seed, 1000)
        ring = in_fixed_order(own, traffic)
        assert [(r.prompt_len, r.out) for r in ring] == want
        assert {r.prompts.tobytes() for r in ring} == \
            {r.prompts.tobytes() for r in own}
        rings[seed] = ring
    assert (rings[7][0].prompts != rings[tiny.SEED][0].prompts).any()


SHAPE = dict(layers=7, first_k_dense_replace=1, hidden_size=5120,
             num_attention_heads=128, q_lora_rank=1536, kv_lora_rank=512,
             qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
             intermediate_size=12288, moe_intermediate_size=1536,
             published={"n_routed_experts": 160}, n_shared_experts=2,
             vocab_size=102400)


def test_weight_bytes_of_the_share():
    """Attention 149.2 M parameters a layer; one expert 23.6 M; every
    step reads attention, the dense MLP, the shared experts, the routers
    and the head."""
    s = F.Shape.of(SHAPE)
    assert F.mla_params(s) == 149_225_472
    assert F.expert_params(s) == 3 * 5120 * 1536
    fixed = F.fixed_weight_bytes(SHAPE)
    assert fixed == (2 * (7 * 149_225_472 + 3 * 5120 * 12288
                          + 6 * 2 * 3 * 5120 * 1536 + 102400 * 5120)
                     + 4 * 6 * 5120 * 160)
    assert 4.0e9 < fixed < 4.2e9


def test_decode_step_counts_latent_cache_and_touched_experts():
    a = F.decode_step(SHAPE, 64, 1000, 0, 0)
    b = F.decode_step(SHAPE, 64, 1001, 0, 0)
    assert b["bytes"] - a["bytes"] == 7 * 2 * 64 * 576
    assert b["flops"] - a["flops"] == 7 * 2 * 64 * 128 * (576 + 512)
    c = F.decode_step(SHAPE, 64, 1000, 10, 3)
    assert c["flops"] - a["flops"] == 2 * 10 * 3 * 5120 * 1536
    assert c["bytes"] - a["bytes"] == 2 * 3 * 3 * 5120 * 1536
    # the published step at 64 rows over 2,560 positions: about 8.9 GB
    # (14.6 of 16 experts touched in each of 6 layers), bytes-bound
    full = F.decode_step(SHAPE, 64, 2560, 64 * 6 * 6 // 10, 88)
    assert 8.5e9 < full["bytes"] < 9.6e9
    assert full["flops"] / 197e12 < full["bytes"] / 819e9


def test_prefill_counts_the_causal_half_and_the_window_sums_requests():
    p1 = F.prefill(SHAPE, 1, 1, 0, 0)
    p2 = F.prefill(SHAPE, 1, 2, 0, 0)
    per_token = p2["flops"] - p1["flops"]
    attn = 7 * 2 * 128 * (128 + 64 + 128) * (3 - 1)
    assert per_token > attn
    work = [(2, 8, 3), (1, 4, 1)]
    assert F.window_steps(work) == [(2, 9), (2, 10)]
    load = {"held_assignments": {"prefill": 5, "decode": 2},
            "experts_touched": {"prefill": 1, "decode": 1}}
    want = (F.prefill(SHAPE, 2, 8, 0, 0)["flops"]
            + F.prefill(SHAPE, 1, 4, 0, 0)["flops"]
            + F.decode_step(SHAPE, 2, 9, 0, 0)["flops"]
            + F.decode_step(SHAPE, 2, 10, 0, 0)["flops"]
            + F.expert_flops(SHAPE, 7))
    assert F.window(SHAPE, work, load) == want


def test_model_config_refuses_a_width_the_program_lacks():
    from bench.systems.mla_moe import model_config

    _, _, cfg, _ = tiny_cell()
    pcfg = model_config(cfg, reduced=True)
    assert pcfg.moe.held == (2, 2) and pcfg.n_layers == 3
    assert pcfg.prefill_tokens == 16
    with pytest.raises(ValueError, match="v_head_dim"):
        model_config(dict(cfg, v_head_dim=32), reduced=True)
    assert dataclasses.replace(pcfg).moe.n_experts == 8


def test_hlo_scopes_reads_the_innermost_named_scope():
    """An instruction's scope is the innermost in its op_name, or else
    the one scope of every instruction that uses it: the TPU's
    ``ragged_dot`` kernel and the copy of the weights it reads carry no
    scope of their own."""
    from bench.systems.mla_moe import hlo_scopes

    hlo = """
  %copy.5 = bf16[2,4]{1,0} copy(bf16[2,4]{1,0} %w), backend_config={}
  %ragged-dot-none.4 = bf16[2,4]{1,0} custom-call(%copy.5, %x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.3 = bf16[2,4]{1,0} fusion(bf16[2,4]{1,0} %ragged-dot-none.4), kind=kLoop, calls=%f, metadata={op_name="jit(decode)/while/body/moe.experts/ragged_dot" source_file="x.py" source_line=3}
  ROOT %add.1 = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b), metadata={op_name="jit(decode)/mla.project/mla.latent_attend/add"}
  %copy.2 = f32[2]{0} copy(f32[2]{0} %x), metadata={op_name="jit(decode)/while/body/dot"}
  %a = f32[2]{0} multiply(f32[2]{0} %copy.2, f32[2]{0} %copy.2), metadata={op_name="jit(decode)/moe.route/mul"}
  %b = f32[2]{0} add(f32[2]{0} %copy.2, f32[2]{0} %copy.2), metadata={op_name="jit(decode)/moe.shared/add"}
"""
    assert hlo_scopes(hlo) == {"fusion.3": "moe.experts",
                               "ragged-dot-none.4": "moe.experts",
                               "copy.5": "moe.experts",
                               "add.1": "mla.latent_attend",
                               "a": "moe.route", "b": "moe.shared"}


def test_scope_reduce_on_a_recorded_trace():
    """``bench/tests/data/small.xplane.pb`` (see ``test_trace_reduce``):
    the Pallas call ``small.1`` of the jitted ``small`` counts in its
    scope when it starts in the window (2 of its 3 runs); the loop
    around the slice sums is left out, its operations counted; a scope
    of a program that did not run reads nothing."""
    import os

    from bench.scope_reduce import reduce_scopes

    path = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb")
    names = {"small.1": "mla.project", "while.2": "moe.route",
             "dynamic-slice_reduce_fusion.2": "moe.experts"}
    window, got = reduce_scopes(path, {"small": names,
                                       "other": {"small.1": "moe.shared"}})
    assert window == pytest.approx(0.289919249, abs=1e-12)
    assert got[("small", "mla.project")][0] == 2
    assert ("small", "moe.route") not in got
    assert got[("small", "moe.experts")][0] == 2 * 8
    assert not any(p == "other" for p, _ in got)
