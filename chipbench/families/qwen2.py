"""Family ``qwen2``: how the benchmark runs a Qwen2 ``config.json``
through the program's vfl-zoo step.

A configuration names its family (``"family"``) as it names its driver
and its reference; the driver and the benchmark's own tests find the
family module by that name (``chipbench/families/<family>.py``), so a
new model family comes as a new file here. An lm family gives:

- ``program_config(cfg)``: the program's ``ModelConfig`` for the
  configuration file ``cfg`` (its ``arch`` and published ``model``);
- ``round_flops(model, vfl, batch, seq)``: the matmul FLOPs of one
  vfl-zoo round (``chipbench/flops.py``'s rules);
- ``SERVER_LEAF``: the server leaf whose change along the shared
  direction gives the program's coefficient of the server's update (an
  informational reading of the check);
- ``TINY``, ``TINY_LIMITS``: the sizes that the benchmark's tests cut
  the ``model`` block to, and the check's limits at those sizes.
"""
from __future__ import annotations

from chipbench import flops

SERVER_LEAF = "w0/embed"

# the published widths cut to a few hundred thousand parameters
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=512)
# set as the chip's are (PERF.md), from the host CPU's readings on seeds
# 1-16 at the tiny sizes: value_gap: program <= 0.543, control >= 1.77,
# half batch >= 1.45; loss_gap: program <= 7.9e-5, half batch >= 1.25e-3,
# altered answer 1e-2; dir_gap: program about 1e-7, unchanged 1;
# w0_sign_gap: program, control and half batch 0, unchanged and no server
# update 1
TINY_LIMITS = {"loss_gap": 8e-4, "value_gap": 1.1, "dir_gap": 1e-3,
               "w0_sign_gap": 0.0}


def program_config(cfg: dict):
    """The program's ModelConfig for the published config in ``cfg``."""
    from repro.configs import get_config
    m = cfg["model"]
    base = get_config(cfg["arch"])
    return base.replace(
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["hidden_size"] // m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], dtype=m["torch_dtype"])


def round_flops(model: dict, vfl: dict, batch: int, seq: int) -> float:
    return flops.zoo_round(model, vfl, batch, seq)
