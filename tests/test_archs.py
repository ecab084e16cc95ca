"""Per-architecture smoke tests: REDUCED variants (2 layers, d_model<=256,
<=4 experts) run one forward/train step and one decode step on CPU,
asserting output shapes and no NaNs. The FULL configs are exercised only by
launch/dryrun.py (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch import steps as step_lib
from repro.models import build_model

pytestmark = pytest.mark.slow  # full model builds/compiles; fast CI skips


def _batch(cfg, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    if cfg.enc_dec:
        batch["frames"] = jnp.asarray(rng.normal(
            size=(B, cfg.encoder_frames, cfg.d_model)).astype(np.float32))
    if cfg.frontend == "vq_stub":
        batch["modality_mask"] = jnp.asarray(
            (rng.random((B, S)) < 0.3).astype(np.int32))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, reduced=True)
    assert cfg.num_layers <= 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4
    model = build_model(cfg)
    state = step_lib.make_train_state(model, jax.random.key(0))
    batch = _batch(cfg)
    logits, aux = model.forward(state.params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    train_step = jax.jit(step_lib.make_train_step(model))
    new_state, (loss, metrics) = train_step(state, batch)
    assert jnp.isfinite(loss)
    # params actually changed
    changed = jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)), state.params, new_state.params)
    assert any(jax.tree.leaves(changed))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B = 2
    frames = None
    if cfg.enc_dec:
        frames = jnp.ones((B, cfg.encoder_frames, cfg.d_model), jnp.float32)
    cache = model.init_cache(params, B, max_len=32, frames=frames)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, cache = model.decode_step(params, cache, tok, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ["deepseek-7b", "rwkv6-1.6b", "hymba-1.5b",
                                  "whisper-small", "chameleon-34b",
                                  "qwen1.5-0.5b", "minicpm-2b", "yi-34b",
                                  "deepseek-v2-lite"])
def test_prefill_decode_consistency(arch):
    """Decoding token-by-token must reproduce the full-sequence logits."""
    import dataclasses
    cfg = get_config(arch, reduced=True)
    if cfg.moe is not None:    # rule out expert-capacity drops
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=16.0))
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    B, S = 2, 8
    toks = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": toks}
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(
            jax.random.key(3), (B, cfg.encoder_frames, cfg.d_model))
    if cfg.frontend == "vq_stub":
        batch["modality_mask"] = jnp.zeros((B, S), jnp.int32)
    full, _ = model.forward(params, batch)
    cache = model.init_cache(params, B, 16, frames=batch.get("frames"))
    outs = []
    for pos in range(S):
        lg, cache = model.decode_step(params, cache, toks[:, pos:pos + 1],
                                      jnp.int32(pos))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                               atol=2e-4, rtol=2e-4)


def test_sliding_window_matches_full_for_short_seq():
    """Window >= seq must equal full attention exactly."""
    cfg = get_config("deepseek-7b", reduced=True)
    model_full = build_model(cfg)
    model_win = build_model(cfg.replace(sliding_window=64))
    params = model_full.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": toks}
    a, _ = model_full.forward(params, batch)
    b, _ = model_win.forward(params, batch)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-4, rtol=2e-4)


def test_num_params_analytic_close_to_actual():
    """ModelConfig.num_params (roofline napkin math) tracks real counts."""
    from repro.utils.trees import tree_size
    for arch in ("qwen1.5-0.5b", "deepseek-7b", "rwkv6-1.6b"):
        cfg = get_config(arch, reduced=True)
        model = build_model(cfg)
        params = jax.eval_shape(model.init, jax.random.key(0))
        actual = tree_size(params)
        est = cfg.num_params()
        assert 0.5 < est / actual < 2.0, (arch, est, actual)
