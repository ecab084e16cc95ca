"""Each configuration's family module (chipbench/families/) gives the
program's model configuration and the FLOPs of a round that the drivers
computed themselves before: pinned as literals, compared with ==."""
import json
import sys

import pytest

from chipbench import common
from conftest import ROOT

QWEN = json.loads((ROOT / "chipbench/configs/qwen1.5-0.5b.zoo-q4.json"
                   ).read_text())
LR = json.loads((ROOT / "chipbench/configs/paper-lr.d6-epsilon.q8.json"
                 ).read_text())


def test_qwen2_program_config_is_the_drivers():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import ModelConfig
    want = ModelConfig(
        name="qwen1.5-0.5b", family="dense", num_layers=24, d_model=1024,
        num_heads=16, num_kv_heads=16, d_ff=2816, vocab_size=151936,
        head_dim=64, qkv_bias=True, qk_norm=False, tie_embeddings=True,
        rope_theta=1000000.0, pos_emb="rope", norm_eps=1e-06,
        sliding_window=None, moe=None, ssm=None, enc_dec=False,
        num_encoder_layers=0, encoder_frames=1500, frontend="none",
        dtype="bfloat16", remat=True, scan_layers=True, chunked_ce=False,
        kv_cache_dtype="model", citation="hf:Qwen/Qwen1.5-0.5B")
    assert common.load_module("families", "qwen2").program_config(QWEN) \
        == want


def test_paper_lr_program_model_is_the_drivers():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import PaperLRConfig
    from repro.core.vfl import PaperLRModel
    model = common.load_module("families", "paper_lr").program_model(LR)
    assert type(model) is PaperLRModel
    assert model.cfg == PaperLRConfig(num_features=2000, num_parties=8)


@pytest.mark.parametrize("family,args,want", [
    # zoo-q05b.b8s64: batch 8 x seq 64
    ("qwen2", (QWEN["model"], QWEN["vfl"], 8, 64), 1430157000704.0),
    # lr-eps.b64: 9 forwards of a 64 x 250 block times a 250 vector
    ("paper_lr", (LR["data"], LR["vfl"], 64), 288000.0),
])
def test_round_flops_are_the_drivers(family, args, want):
    assert common.load_module("families", family).round_flops(*args) == want
