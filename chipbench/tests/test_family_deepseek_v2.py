"""Family ``deepseek_v2`` (chipbench/families/deepseek_v2.py) at the
cell's sizes: the program's model configuration and the FLOPs of a
round, pinned as literals and compared with ==, the published values
it refuses to run as something else, and the file's two copies of the
configuration held equal."""
import json
import sys

import pytest

from chipbench import common
from conftest import ROOT

DSV2L = json.loads((ROOT / "chipbench/configs/deepseek-v2-lite.ep8.zoo-q4"
                    ".json").read_text())


def test_program_config_is_the_cells():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import MLAConfig, ModelConfig, MoEConfig
    want = ModelConfig(
        name="deepseek-v2-lite", family="moe", num_layers=9, d_model=2048,
        num_heads=16, num_kv_heads=16, d_ff=10944, vocab_size=12800,
        head_dim=0, qkv_bias=False, qk_norm=False, tie_embeddings=False,
        rope_theta=10000.0, pos_emb="rope", norm_eps=1e-06,
        sliding_window=None,
        moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                      capacity_factor=1.25, router_aux_coef=0.0,
                      dispatch="dropless", num_shared_experts=2,
                      experts_held=8, first_expert=0),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128, rope_factor=40.0,
                      original_max_position=4096, beta_fast=32.0,
                      beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        first_k_dense=1, ssm=None, enc_dec=False, num_encoder_layers=0,
        encoder_frames=1500, frontend="none", dtype="bfloat16", remat=True,
        scan_layers=True, chunked_ce=False, kv_cache_dtype="model",
        citation="hf:deepseek-ai/DeepSeek-V2-Lite")
    got = common.load_module("families", "deepseek_v2").program_config(DSV2L)
    assert got == want


def test_round_flops_at_the_cells_sizes():
    # zoo-dsv2l.b8s256: batch 8 x seq 256; 817,364,992 FLOPs a token in
    # the server's linear layers (6 x 8 / 64 routed experts a token)
    fam = common.load_module("families", "deepseek_v2")
    assert fam.forward_per_token_linear(DSV2L["model"]) == 817364992.0
    assert fam.round_flops(DSV2L["model"], DSV2L["vfl"], 8, 256) \
        == 5097052438528.0


@pytest.mark.parametrize("key,value", [("norm_topk_prob", True),
                                       ("scoring_func", "sigmoid"),
                                       ("topk_method", "group_limited_greedy")])
def test_program_config_refuses_what_it_does_not_implement(key, value):
    cfg = json.loads(json.dumps(DSV2L))
    cfg["model"][key] = value
    fam = common.load_module("families", "deepseek_v2")
    with pytest.raises(SystemExit, match=key):
        fam.program_config(cfg)


def test_model_block_is_the_top_level():
    # the file gives the configuration at its top level, key for key with
    # the catalog's config, and again as the `model` block that the
    # zoo_step driver and the reference read: the two may not drift apart
    assert {k: DSV2L[k] for k in DSV2L["model"]} == DSV2L["model"]
    for k, n in DSV2L["published"].items():
        assert k in DSV2L["reduced"] or DSV2L[k] == n
