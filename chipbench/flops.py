"""Operations an AsyREVEL round needs, whatever implements it: matmul
FLOPs (2 per multiply-add) of the forwards the algorithm calls for.
Random draws, elementwise work, norms, softmax and copies are left out.

A round of the vfl-zoo step makes three server forwards (h, h_bar,
h_hat) and q + 1 party-tower forwards (every party's c from its stale
block, and the activated party's perturbed c_hat). A round of the
paper's LR makes q + 1 party forwards (x_m @ w_m); its server is a sum.
"""
from __future__ import annotations


def qwen2_forward_per_token_linear(m: dict) -> float:
    """Linear layers and the (tied) head of one token's forward."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // H
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d \
        + 3 * d * m["intermediate_size"]
    return 2.0 * (L * per_layer + d * m["vocab_size"])


def qwen2_attention_per_sequence(m: dict, seq: int) -> float:
    """Causal scores and weighted values of one sequence: QK^T and PV at
    half of S^2 each."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    return L * 2 * (2.0 * seq * seq * d / 2)


def party_tower_per_token(d_q: int, hidden: int) -> float:
    return 2.0 * (d_q * hidden + hidden * d_q)


def zoo_round(m: dict, v: dict, batch: int, seq: int) -> float:
    server = (qwen2_forward_per_token_linear(m) * batch * seq
              + qwen2_attention_per_sequence(m, seq) * batch)
    d_q = m["hidden_size"] // v["num_parties"]
    party = party_tower_per_token(d_q, v["party_hidden"]) * batch * seq
    return 3 * server + (v["num_parties"] + 1) * party


def lr_round(features: int, q: int, batch: int) -> float:
    pad = -(-features // q)
    return (q + 1) * 2.0 * batch * pad
