"""The FLOP function against hand counts and the program's own parameter
count."""
import json

import pytest

from chipbench import flops
from conftest import ROOT

QWEN = json.loads((ROOT / "chipbench/configs/qwen1.5-0.5b.zoo-q4.json"
                   ).read_text())
LR = json.loads((ROOT / "chipbench/configs/paper-lr.d6-epsilon.q8.json"
                 ).read_text())


def test_linear_flops_per_token_is_twice_the_matmul_parameters():
    m = QWEN["model"]
    # hand count: 24 x (4 x 1024^2 + 3 x 1024 x 2816) + 1024 x 151,936
    # = 463,863,808 multiply-adds a token
    assert flops.qwen2_forward_per_token_linear(m) == 2 * 463_863_808
    assert flops.qwen2_forward_per_token_linear(m) == pytest.approx(9.28e8,
                                                                   rel=1e-3)


def test_linear_flops_match_the_programs_parameter_count():
    import sys
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    cfg = get_config("qwen1.5-0.5b")
    # num_params counts the QKV biases, which are elementwise work
    biases = cfg.num_layers * 3 * cfg.num_heads * cfg.resolved_head_dim
    assert flops.qwen2_forward_per_token_linear(QWEN["model"]) == \
        2 * (cfg.num_params() - biases)


def test_zoo_round_b8s64():
    # 3 x (512 x 9.28e8 + 8 x 24 x 64^2 x 1024 x 2) + 5 x 512 x 131,072
    got = flops.zoo_round(QWEN["model"], QWEN["vfl"], 8, 64)
    assert got == pytest.approx(1.43e12, rel=5e-3)


def test_lr_round_b64():
    # 9 forwards of a 64 x 250 block times a 250 vector
    assert flops.lr_round(LR["data"]["features"],
                          LR["vfl"]["num_parties"], 64) == 9 * 2 * 64 * 250
    assert flops.lr_round(2000, 8, 64) == pytest.approx(2.9e5, rel=1e-2)
