"""Operations and bytes the model's mathematics needs, from its shapes.

Counted from the configuration's file (``model`` section), never from the
program, so a change to the program cannot move the yardstick.

* ``matmul_params``: weights that multiply each token (projections, MLP,
  output head; not the embedding lookup, norms or biases).
* ``train_flops_per_token``: forward and backward, 3 x (2 x matmul_params
  + the sequence mixer's own operations); recomputation (remat) is not
  counted. Causal attention counts the keys each query sees, (S + 1) / 2 on
  average; the SSD mixer counts its recurrence, 4 x heads x head_dim x
  state per token (update and read of the state).
* ``decode_bytes``: the least one decode call of a batch must move: the
  weights once in bf16, the live cache read in bf16 (attention: keys and
  values of the positions so far; SSD: state and convolution window, read
  and written), and the logits written in bf16.
* ``decode_flops``: the operations of one decode call of a batch.
"""
from __future__ import annotations

from typing import Any, Dict

BF16 = 2


def _ssm_dims(m):
    d_in = m["ssm_expand"] * m["d_model"]
    return d_in, d_in // m["ssm_head_dim"], m["ssm_ngroups"], m["ssm_state"]


def padded_vocab(m) -> int:
    k = m["pad_vocab_multiple"]
    return -(-m["vocab_size"] // k) * k


def matmul_params(m: Dict[str, Any]) -> int:
    D, L = m["d_model"], m["num_layers"]
    head = D * padded_vocab(m)
    if m["family"] == "ssm":
        d_in, H, G, N = _ssm_dims(m)
        per = D * (2 * d_in + 2 * G * N + H) + d_in * D
    elif m["family"] == "dense":
        H, KH, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
        mlp = (3 if m["act"] == "swiglu" else 2) * D * m["d_ff"]
        per = D * (H + 2 * KH) * hd + H * hd * D + mlp
    else:
        raise KeyError(f"no operation count for family {m['family']!r}")
    return L * per + head


def mixer_flops_per_token(m: Dict[str, Any], keys: float) -> float:
    """The sequence mixer's own forward operations per token and layer,
    for a token that sees ``keys`` positions (attention only)."""
    if m["family"] == "ssm":
        d_in, H, G, N = _ssm_dims(m)
        conv = 2 * m["ssm_conv"] * (d_in + 2 * G * N)
        return 4.0 * H * m["ssm_head_dim"] * N + conv
    return 4.0 * m["num_heads"] * m["head_dim"] * keys


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    fwd = (2.0 * matmul_params(m)
           + m["num_layers"] * mixer_flops_per_token(m, (seq + 1) / 2))
    return 3.0 * fwd


def cache_bytes(m: Dict[str, Any], batch: int, live: float) -> float:
    """Cache a decode call must read (and, for SSD state, write) at the
    bf16 floor, with ``live`` positions so far."""
    L = m["num_layers"]
    if m["family"] == "ssm":
        d_in, H, G, N = _ssm_dims(m)
        state = H * m["ssm_head_dim"] * N + (m["ssm_conv"] - 1) * (
            d_in + 2 * G * N)
        return 2.0 * BF16 * L * batch * state
    return BF16 * L * batch * 2 * m["num_kv_heads"] * m["head_dim"] * live


def decode_bytes(m: Dict[str, Any], batch: int, live: float) -> float:
    return (BF16 * matmul_params(m) + cache_bytes(m, batch, live)
            + BF16 * batch * padded_vocab(m))


def decode_flops(m: Dict[str, Any], batch: int, live: float) -> float:
    return batch * (2.0 * matmul_params(m)
                    + m["num_layers"] * mixer_flops_per_token(m, live))
