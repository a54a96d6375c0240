"""Operations and bytes that the measured work of a latent-attention
mixture-of-experts engine (DeepSeek-V2's configuration keys) needs, from
its shapes and the engine's expert counters.

A chip holds the configuration's share: ``layers`` layers, the first
``first_k_dense_replace`` of them with a SwiGLU MLP of
``intermediate_size``, the rest with a router over the published number
of experts, the shared experts and ``n_routed_experts`` held experts.
Expert work is counted from the engine's counters, not assumed: the
routed assignments that landed on held experts (each a token through
one expert) and the held experts touched (each one read of an expert's
weights).  Decode attends in the latent space (the query folded through
``wk_b``, the output through ``wv_b``) over the valid cache positions;
prefill re-expands keys and values per head and keeps the causal half of
the score matrix.  Rooflines and model FLOP utilisation divide these by
times from the device trace.
"""

from __future__ import annotations

import dataclasses

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    dense: int
    d: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ff: int
    ff_expert: int
    experts: int        # the router's width
    shared: int
    vocab: int

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        return cls(cfg["layers"], cfg["first_k_dense_replace"],
                   cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["q_lora_rank"], cfg["kv_lora_rank"],
                   cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"], cfg["intermediate_size"],
                   cfg["moe_intermediate_size"],
                   cfg["published"]["n_routed_experts"],
                   cfg["n_shared_experts"], cfg["vocab_size"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense

    @property
    def latent(self) -> int:
        """Cache entries a position holds per layer (ckv and krope)."""
        return self.kv_rank + self.rope


def mla_params(s: Shape) -> int:
    """One layer's attention weights."""
    return (s.d * s.q_rank + s.q_rank * s.heads * (s.nope + s.rope)
            + s.d * (s.kv_rank + s.rope) + s.kv_rank * s.heads * s.nope
            + s.kv_rank * s.heads * s.v_dim + s.heads * s.v_dim * s.d)


def expert_params(s: Shape) -> int:
    """One routed expert's weights (up, gate, down)."""
    return 3 * s.d * s.ff_expert


def fixed_weight_bytes(cfg: dict) -> int:
    """The weights every forward pass reads whatever the routing: every
    layer's attention, the dense MLPs, each expert layer's router
    (float32) and shared experts, and the output head (the token table
    gives only its looked-up rows)."""
    s = Shape.of(cfg)
    return (BF16 * (s.layers * mla_params(s) + s.dense * 3 * s.d * s.ff
                    + s.moe_layers * s.shared * expert_params(s)
                    + s.vocab * s.d)
            + F32 * s.moe_layers * s.d * s.experts)


def _dense_token_flops(s: Shape) -> int:
    """Per token, all layers: what does not depend on the routing or the
    context (projections of attention, dense MLPs, routers, shared
    experts)."""
    proj = 2 * (s.d * s.q_rank + s.q_rank * s.heads * (s.nope + s.rope)
                + s.d * (s.kv_rank + s.rope) + s.heads * s.v_dim * s.d)
    return (s.layers * proj + s.dense * 2 * 3 * s.d * s.ff
            + s.moe_layers * 2 * (s.d * s.experts
                                  + s.shared * expert_params(s)))


def expert_flops(cfg: dict, assignments: int) -> int:
    """``assignments`` routed rows through one held expert each."""
    return 2 * expert_params(Shape.of(cfg)) * assignments


def expert_bytes(cfg: dict, touched: int) -> int:
    """``touched`` reads of a held expert's weights."""
    return BF16 * expert_params(Shape.of(cfg)) * touched


def latent_attend(cfg: dict, batch: int, cache_len: int) -> dict:
    """One decode step's attention in the latent space, all layers:
    scores over the latent and rope dims and values over the latent,
    for every head, at ``cache_len`` valid positions, which it reads
    once."""
    s = Shape.of(cfg)
    return {"flops": s.layers * 2 * batch * s.heads * cache_len
            * (s.latent + s.kv_rank),
            "bytes": s.layers * BF16 * batch * cache_len * s.latent}


def decode_step(cfg: dict, batch: int, cache_len: int, assignments: int,
                touched: int) -> dict:
    """One decode step of ``batch`` rows over ``cache_len`` valid
    positions (the new token's included), with the step's routed
    assignments to held experts and held experts touched (all layers)."""
    s = Shape.of(cfg)
    absorb = s.layers * 2 * s.heads * s.kv_rank * (s.nope + s.v_dim)
    attn = latent_attend(cfg, batch, cache_len)
    flops = (batch * (_dense_token_flops(s) + absorb) + attn["flops"]
             + expert_flops(cfg, assignments) + 2 * batch * s.d * s.vocab)
    return {"flops": flops,
            "bytes": fixed_weight_bytes(cfg) + expert_bytes(cfg, touched)
            + attn["bytes"]}


def prefill(cfg: dict, batch: int, seq: int, assignments: int,
            touched: int) -> dict:
    """One prefill of ``batch`` prompts of ``seq`` tokens (logits of the
    last position only), with its routed assignments to held experts and
    held experts touched."""
    s = Shape.of(cfg)
    tokens = batch * seq
    expand = s.layers * 2 * s.kv_rank * s.heads * (s.nope + s.v_dim)
    attn = (s.layers * 2 * batch * s.heads * (s.nope + s.rope + s.v_dim)
            * seq * (seq + 1) // 2)
    flops = (tokens * (_dense_token_flops(s) + expand) + attn
             + expert_flops(cfg, assignments) + 2 * batch * s.d * s.vocab)
    cache_write = s.layers * BF16 * tokens * s.latent
    return {"flops": flops,
            "bytes": fixed_weight_bytes(cfg) + expert_bytes(cfg, touched)
            + cache_write}


def window_steps(work):
    """(rows, valid cache length) of every decode step of the requests
    ``work`` ((rows, prompt, tokens) each): the first token comes from
    the prefill."""
    return [(b, p + t + 1) for b, p, n in work for t in range(n - 1)]


def window(cfg: dict, work, load: dict) -> int:
    """Operations of every request of ``work``, prefill and decode, with
    the window's expert counters ``load`` (``held_assignments`` and
    ``experts_touched`` by phase) spread over its prefills and steps."""
    steps = window_steps(work)
    flops = 0
    for b, p, _ in work:
        flops += prefill(cfg, b, p, 0, 0)["flops"]
    for b, c in steps:
        flops += decode_step(cfg, b, c, 0, 0)["flops"]
    return flops + expert_flops(cfg, sum(load["held_assignments"].values()))
