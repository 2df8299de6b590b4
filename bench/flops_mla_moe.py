"""Operations and bytes of DeepSeek-V3's block (family moe with MLA) as one
chip's expert share of it, from its shapes.

Counted from the configuration's file (``model`` section), never from the
program, so a change to the program cannot move the yardstick. ``held`` is
``experts_held`` (all ``num_experts`` when 0).

* ``prefill_flops``: one one-call prefill of ``batch`` rows padded to
  ``seq`` positions: 2 x the weights that multiply each position (MLA's
  projections, with W_kvb expanding the latent at every position; the
  dense layers' MLP; the router; the shared expert; the routed experts at
  their expected held share, ``experts_per_token`` x held / num_experts
  experts a position), causal attention over (seq + 1) / 2 keys a query
  (q.k over nope + rope dims, p.v over v dims, every head), and the output
  head at one position a row.
* ``decode_bytes``: the least one decode call of ``batch`` tokens must
  move: the bf16 weights once, without the embedding (a lookup), with the
  held experts that some token is routed to (of ``held``, each routed to
  with chance k / num_experts per token: held x (1 - (1 - k/E)^batch)
  expected); the bf16 latent cache (c_kv and k_rope) at ``live`` positions
  of every layer; the bf16 logits.
"""
from __future__ import annotations

from typing import Any, Dict

BF16 = 2


def padded_vocab(m) -> int:
    k = m["pad_vocab_multiple"]
    return -(-m["vocab_size"] // k) * k


def held(m) -> int:
    return m["experts_held"] or m["num_experts"]


def mla_params(m) -> int:
    D, H = m["d_model"], m["num_heads"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    return (D * qr + qr * H * (nope + rope) + D * (kvr + rope)
            + kvr * H * (nope + vd) + H * vd * D)


def expert_params(m) -> int:
    return 3 * m["d_model"] * m["moe_d_ff"]


def _layer_params(m, experts_per_layer: float) -> float:
    """Weights of every layer, with ``experts_per_layer`` routed experts in
    each expert layer."""
    L, kd = m["num_layers"], m["first_k_dense"]
    moe = (m["d_model"] * m["num_experts"]
           + (m["num_shared_experts"] + experts_per_layer) * expert_params(m))
    return L * mla_params(m) + kd * 3 * m["d_model"] * m["d_ff"] \
        + (L - kd) * moe


def prefill_flops(m: Dict[str, Any], batch: int, seq: int) -> float:
    per_pos = 2.0 * _layer_params(
        m, m["experts_per_token"] * held(m) / m["num_experts"])
    qk = m["qk_nope_dim"] + m["qk_rope_dim"]
    attn = m["num_layers"] * 2.0 * m["num_heads"] * (qk + m["v_head_dim"]) \
        * (seq + 1) / 2
    head = 2.0 * m["d_model"] * padded_vocab(m) * batch
    return batch * seq * (per_pos + attn) + head


def touched_experts(m: Dict[str, Any], batch: int) -> float:
    p = m["experts_per_token"] / m["num_experts"]
    return held(m) * (1.0 - (1.0 - p) ** batch)


def decode_bytes(m: Dict[str, Any], batch: int, live: float) -> float:
    weights = _layer_params(m, touched_experts(m, batch)) \
        + m["d_model"] * padded_vocab(m)
    cache = m["num_layers"] * batch * (m["kv_lora_rank"] + m["qk_rope_dim"]) \
        * live
    return BF16 * (weights + cache + batch * padded_vocab(m))
