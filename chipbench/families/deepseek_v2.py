"""Family ``deepseek_v2``: how the benchmark runs a DeepSeek-V2
``config.json`` through the program's vfl-zoo step: latent attention
(MLA) with YaRN rope in every layer, ``first_k_dense_replace`` dense
layers, then layers of routed experts (softmax scores, greedy top-k)
beside shared experts, an untied head.

Beside the published keys the ``model`` block carries ``experts_held``:
the routed experts this chip holds (experts 0 to experts_held - 1) under
the configuration's expert-parallel deployment; the router still scores
all ``n_routed_experts``. The functions are those of ``qwen2.py``.
"""
from __future__ import annotations

from chipbench.flops import party_tower_per_token

SERVER_LEAF = "w0/lm_head"

# the published kinds of layer at widths of a few dozen: a dense layer,
# two MoE layers of 4 held experts out of 16, top-4, 2 shared experts
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
            experts_held=4, vocab_size=512)
# set as the chip's are (PERF.md), from the host CPU's readings on seeds
# 1-16 at the tiny sizes: value_gap: program <= 0.51, control >= 2.89,
# half batch >= 3.88; loss_gap: program <= 8.3e-5, control >= 2.05e-4,
# half batch >= 1.71e-3; dir_gap: program within 2.1e-7 of 0, unchanged
# 1; w0_sign_gap: program, control and half batch 0, unchanged 1
TINY_LIMITS = {"loss_gap": 8e-4, "value_gap": 1.1, "dir_gap": 1e-3,
               "w0_sign_gap": 0.0}

# what the program implements of the published config; anything else
# is refused rather than run as something it is not
SUPPORTED = {"scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
             "q_lora_rank": None, "attention_bias": False,
             "hidden_act": "silu", "routed_scaling_factor": 1,
             "norm_topk_prob": False}


def program_config(cfg: dict):
    """The program's ModelConfig for the published config in ``cfg``."""
    m = cfg["model"]
    for k, want in SUPPORTED.items():
        if m.get(k, want) != want:
            raise SystemExit(f"deepseek_v2: {k} = {m[k]!r} is not "
                             f"implemented (have {want!r})")
    import dataclasses

    from repro.configs import get_config
    base = get_config(cfg["arch"])
    rs = m["rope_scaling"]
    assert rs["type"] == "yarn"
    mla = dataclasses.replace(
        base.mla, kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_factor=float(rs["factor"]),
        original_max_position=rs["original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])
    moe = dataclasses.replace(
        base.moe, num_experts=m["n_routed_experts"],
        top_k=m["num_experts_per_tok"],
        d_ff_expert=m["moe_intermediate_size"],
        num_shared_experts=m["n_shared_experts"],
        experts_held=m["experts_held"], first_expert=0)
    return base.replace(
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], tie_embeddings=m["tie_word_embeddings"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
        first_k_dense=m["first_k_dense_replace"], mla=mla, moe=moe,
        dtype=m["torch_dtype"])


def forward_per_token_linear(m: dict) -> float:
    """Linear layers of one token's server forward: MLA, the dense
    layers' MLP, the router, the shared experts, the held experts' share
    of the top-k slots (num_experts_per_tok x held / routed of a token),
    and the sliced head."""
    d, L, H = m["hidden_size"], m["num_hidden_layers"], \
        m["num_attention_heads"]
    r, nope, rope, vd = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    dense = m["first_k_dense_replace"]
    f = m["moe_intermediate_size"]
    mla = (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd)
           + H * vd * d)
    routed = (m["num_experts_per_tok"] * m["experts_held"]
              / m["n_routed_experts"])
    moe = (d * m["n_routed_experts"] + 3 * d * f * m["n_shared_experts"]
           + routed * 3 * d * f)
    return 2.0 * (L * mla + dense * 3 * d * m["intermediate_size"]
                  + (L - dense) * moe + d * m["vocab_size"])


def attention_per_sequence(m: dict, seq: int) -> float:
    """Causal scores (qk width nope + rope) and weighted values (width
    v_head_dim) of one sequence, each at half of S^2."""
    H, L = m["num_attention_heads"], m["num_hidden_layers"]
    width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return L * 2.0 * (seq * seq / 2) * H * width


def round_flops(model: dict, vfl: dict, batch: int, seq: int) -> float:
    """Three server forwards and q + 1 party-tower forwards
    (``chipbench/flops.py``'s rules), the routed experts counted over
    the held share only."""
    server = (forward_per_token_linear(model) * batch * seq
              + attention_per_sequence(model, seq) * batch)
    d_q = model["hidden_size"] // vfl["num_parties"]
    party = party_tower_per_token(d_q, vfl["party_hidden"]) * batch * seq
    return 3 * server + (vfl["num_parties"] + 1) * party
