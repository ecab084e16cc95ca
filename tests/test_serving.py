"""Serving-path tests: int8 KV cache, rolling windows, launcher smoke."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model


@pytest.mark.slow
def test_int8_cache_matches_bf16_cache_argmax():
    cfg = get_config("deepseek-7b", reduced=True)
    m1 = build_model(cfg)
    m2 = build_model(cfg.replace(kv_cache_dtype="int8"))
    params = m1.init(jax.random.key(0))
    B, S = 2, 10
    toks = jax.random.randint(jax.random.key(1), (B, S), 0,
                              cfg.vocab_size)
    c1 = m1.init_cache(params, B, 16)
    c2 = m2.init_cache(params, B, 16)
    assert c2["layers"]["kv"]["k"].dtype == jnp.int8
    for pos in range(S):
        l1, c1 = m1.decode_step(params, c1, toks[:, pos:pos + 1],
                                jnp.int32(pos))
        l2, c2 = m2.decode_step(params, c2, toks[:, pos:pos + 1],
                                jnp.int32(pos))
    np.testing.assert_array_equal(np.asarray(jnp.argmax(l1, -1)),
                                  np.asarray(jnp.argmax(l2, -1)))
    assert float(jnp.max(jnp.abs(l1 - l2))) < 0.1


def test_int8_cache_is_smaller():
    from repro.utils.trees import tree_bytes
    cfg = get_config("deepseek-7b", reduced=True).replace(dtype="bfloat16")
    m1 = build_model(cfg)
    m2 = build_model(cfg.replace(kv_cache_dtype="int8"))
    params = m1.init(jax.random.key(0))
    c1 = m1.init_cache(params, 2, 64)
    c2 = m2.init_cache(params, 2, 64)
    assert tree_bytes(c2) < 0.6 * tree_bytes(c1)


@pytest.mark.slow
def test_sliding_window_rolling_cache_decode():
    """Decode past the window: the rolling buffer must keep only the last
    `window` positions and logits must match a full-cache model restricted
    to the same window."""
    cfg = get_config("deepseek-7b", reduced=True)
    win = 8
    m_win = build_model(cfg.replace(sliding_window=win))
    params = m_win.init(jax.random.key(0))
    B, S = 1, 14
    toks = jax.random.randint(jax.random.key(1), (B, S), 0,
                              cfg.vocab_size)
    cache = m_win.init_cache(params, B, max_len=S)
    assert cache["layers"]["kv"]["k"].shape[2] == win  # rolling buffer
    for pos in range(S):
        logits, cache = m_win.decode_step(params, cache,
                                          toks[:, pos:pos + 1],
                                          jnp.int32(pos))
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_train_launcher_smoke(tmp_path):
    from repro.launch import train as train_mod
    loss = train_mod.main([
        "--arch", "qwen1.5-0.5b", "--reduced", "--steps", "6",
        "--batch-size", "2", "--seq-len", "16",
        "--ckpt-dir", str(tmp_path)])
    assert np.isfinite(loss)
    from repro.checkpoint.ckpt import latest_step
    assert latest_step(str(tmp_path)) == 6


@pytest.mark.slow
def test_train_launcher_vfl_zoo_smoke():
    from repro.launch import train as train_mod
    run = train_mod.main([
        "--arch", "qwen1.5-0.5b", "--reduced", "--steps", "6",
        "--batch-size", "2", "--seq-len", "16", "--mode", "vfl-zoo",
        "--parties", "4"])
    assert len(run.losses) == len(run.step_s) == 6
    assert np.isfinite(run.losses).all()


def test_serve_launcher_smoke():
    from repro.launch import serve as serve_mod
    out = serve_mod.main(["--arch", "rwkv6-1.6b", "--reduced",
                          "--batch", "2", "--prompt-len", "6",
                          "--gen-len", "3"])
    assert out.shape == (2, 3)


# ------------------- federated serving (serving/federated.py) -------------

from repro.runtime.problem import build_problem  # noqa: E402
from repro.serving.federated import (FederatedServingEngine,  # noqa: E402
                                     ServeRequest)


def _spec(codec="f32", kind="lr", parties=4):
    spec = {"kind": kind, "parties": parties, "features": 32, "samples": 64,
            "batch": 8, "seed": 0, "vfl": {"mu": 1e-3}}
    if codec != "f32":
        spec["vfl"]["codec"] = codec
    return spec


def _lr_params(prob, seed=7):
    """Nonzero LR blocks (zero-init would serve all-zero predictions)."""
    q = prob.model.num_parties
    keys = jax.random.split(jax.random.key(seed), q)
    return [{"w": jax.random.normal(keys[m], (prob.model.pad,))}
            for m in range(q)]


def _serve(prob, ids, *, slots=8, cache=2048, party_params=None,
           channel=None, versions=None):
    eng = FederatedServingEngine.from_problem(
        prob, channel=channel, slots=slots, cache_entries=cache,
        party_params=party_params, versions=versions)
    for i, sid in enumerate(ids):
        eng.submit(ServeRequest(rid=i, sample_id=int(sid)))
    eng.run()
    eng.validate_wire()
    return eng


def _preds(done):
    return {r.rid: r.prediction for r in done}


@pytest.mark.serving
@pytest.mark.parametrize("codec", ["f32", "bf16", "int8"])
def test_federated_batched_vs_sequential_bitwise(codec):
    """Batched serving (with mid-stream admission: 20 requests > 8
    slots) is bitwise the one-at-a-time engine, per codec — the
    per-sample jitted forward makes batching purely a wire concern."""
    prob = build_problem(_spec(codec, kind="fcn"))
    ids = np.random.default_rng(2).integers(0, 64, 20)
    eng_b = _serve(prob, ids, slots=8)
    eng_1 = _serve(prob, ids, slots=1)
    assert _preds(eng_b.completed) == _preds(eng_1.completed)
    assert len(eng_b.completed) == 20 and eng_b.steps < eng_1.steps


@pytest.mark.serving
def test_federated_f32_matches_local_model_bitwise():
    """f32 serving = the centralized model.predict, bit for bit: the
    wire adds nothing to an uncompressed release."""
    prob = build_problem(_spec())
    model, pp = prob.model, None
    pp = _lr_params(prob)
    ids = np.arange(16)
    eng = _serve(prob, ids, party_params=pp)
    from repro.core import async_host
    server_key, _, _ = async_host.trainer_keys(prob.seed, 4)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *pp)
    local = np.asarray(model.predict(model.init_server(server_key),
                                     stacked, jnp.asarray(prob.X[ids])))
    served = np.asarray([_preds(eng.completed)[i] for i in range(16)],
                        np.float32)
    assert set(served) <= {-1.0, 1.0}        # nonzero blocks: real signs
    np.testing.assert_array_equal(local, served)


@pytest.mark.serving
def test_answer_cache_hits_and_version_bump():
    prob = build_problem(_spec())
    pp = _lr_params(prob)
    ids = np.concatenate([np.arange(8)] * 3)      # 8 users, 3 visits
    eng = _serve(prob, ids, party_params=pp)
    m = eng.metrics()
    # visits 2 and 3 hit for every party; only visit 1 crossed the wire
    assert m["cache_hits"] == 2 * 8 * 4 and m["cache_misses"] == 8 * 4
    assert eng._analytic["serve_down"] == 4 * 8 * 4
    first = _preds(eng.completed)
    assert all(first[i] == first[i + 8] == first[i + 16] for i in range(8))
    # rotate party 0's block: version bump invalidates by KEY, so the
    # same sample ids miss, re-query, and reflect the new params
    new_w0 = {"w": -pp[0]["w"]}
    eng.set_party_params(0, new_w0, version=1)
    for i, sid in enumerate(np.arange(8)):
        eng.submit(ServeRequest(rid=100 + i, sample_id=int(sid)))
    eng.run()
    eng.validate_wire()
    ref = _serve(build_problem(_spec()), np.arange(8),
                 party_params=[new_w0] + pp[1:])
    after = _preds(eng.completed)
    assert all(after[100 + i] == _preds(ref.completed)[i]
               for i in range(8))


@pytest.mark.serving
@pytest.mark.parametrize("codec", ["f32", "bf16", "int8"])
def test_wire_bytes_match_analytic(codec):
    """Full batches, no cache: measured bytes/prediction equals the
    closed form (validate_wire already pins the per-kind counters)."""
    from repro.core.comms import serving_bytes_per_prediction
    prob = build_problem(_spec(codec))
    eng = _serve(prob, np.arange(32), slots=8, cache=0)
    assert eng.metrics()["bytes_per_prediction"] == \
        serving_bytes_per_prediction(8, 4, codec)


@pytest.mark.serving
def test_serving_transcript_feeds_privacy_attacks():
    """A recorded serving transcript is auditable with the training
    attacks unchanged: the exposure derives from the observed kinds and
    label inference reads the batched c_up answers directly."""
    from repro.core.privacy import (label_inference_from_uploads,
                                    serving_exposure_from_transcript)
    from repro.core.wire import RecordingChannel
    prob = build_problem(_spec())
    ch = RecordingChannel()
    eng = _serve(prob, np.arange(16), party_params=_lr_params(prob),
                 channel=ch)
    assert len(eng.completed) == 16
    exp = serving_exposure_from_transcript(ch.transcript)
    assert exp["serve_query_ids"] and exp["function_values"]
    assert not exp["intermediate_grads"] and not exp["model_params"]
    assert exp["messages"]["c_up"] == exp["messages"]["serve_down"]
    atk = label_inference_from_uploads(ch.transcript, prob.y)
    assert atk["samples"] == 16 and 0.0 <= atk["accuracy"] <= 1.0


@pytest.mark.serving
def test_serving_rejects_dp_defended_exchange():
    spec = _spec()
    spec["vfl"]["dp"] = {"epsilon": 2.0, "clip": 1.0,
                         "noise_multiplier": 1.0}
    with pytest.raises(ValueError, match="deterministic keyless"):
        FederatedServingEngine.from_problem(build_problem(spec))


@pytest.mark.serving
def test_fused_slot_reset_bitwise_equals_per_slot():
    from repro.serving.engine import _reset_slots
    key = jax.random.key(3)
    cache = {"k": jax.random.normal(key, (2, 4, 3, 5)),
             "pos": jax.random.normal(jax.random.key(4), (2, 4)),
             "scalar": jnp.float32(7.0)}
    mask = np.array([True, False, True, False])
    legacy = cache
    for s in np.nonzero(mask)[0]:
        legacy = jax.tree.map(
            lambda a, s=s: a.at[:, s].set(jnp.zeros_like(a[:, s]))
            if a.ndim >= 2 else a, legacy)
    fused = _reset_slots(cache, jnp.asarray(mask))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), legacy, fused)


@pytest.mark.serving
@pytest.mark.slow
def test_engine_sampling_is_slot_position_independent():
    """Non-greedy decoding keys each token by (rid, tokens generated):
    a request's sampled continuation must not depend on how many slots
    the engine has or who shares the batch (incl. mid-stream
    admission)."""
    from repro.serving.engine import Request, ServingEngine
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 6, 3)]

    def gen(slots):
        eng = ServingEngine(model, params, slots=slots, max_len=32,
                            greedy=False, seed=11)
        for rid, pr in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=pr, max_new_tokens=5))
        done = eng.run()
        return {r.rid: r.out_tokens for r in done}

    a, b = gen(2), gen(3)      # slots=2 forces mid-stream admission
    assert a == b


@pytest.mark.serving
@pytest.mark.runtime
@pytest.mark.slow
def test_tcp_serving_bitwise_equals_memory(tmp_path):
    """The TCP serving round — real party processes restoring
    CHECKPOINTED blocks and answering over sockets — serves bitwise the
    in-memory engine given the same blocks and versions."""
    import os

    from repro.checkpoint import save_checkpoint
    from repro.configs.base import RuntimeConfig
    from repro.runtime.serving import run_tcp_serving

    spec = _spec(codec="int8", parties=2)
    prob = build_problem(spec)
    pp = _lr_params(prob)
    for m in range(2):
        save_checkpoint(os.path.join(str(tmp_path), f"party{m}"), 5,
                        pp[m], {"party": m})
    ids = np.random.default_rng(3).integers(0, 64, 12)
    res = run_tcp_serving(spec, ids, cfg=RuntimeConfig(deadline_s=120.0),
                          slots=4, ckpt_root=str(tmp_path))
    assert all(p["version"] == 5 and not p["aborted"]
               for p in res["parties"].values())
    ref = _serve(prob, ids, slots=4, party_params=pp, versions=[5, 5])
    assert res["predictions"] == [(r.sample_id, r.prediction)
                                  for r in sorted(ref.completed,
                                                  key=lambda r: r.rid)]
    assert res["analytic"] == ref._analytic


@pytest.mark.serving
def test_serve_launcher_federated_smoke():
    from repro.launch import train as train_mod
    served = train_mod.main([
        "--arch", "qwen1.5-0.5b", "--reduced", "--mode", "vfl-zoo",
        "--parties", "4", "--serve", "8", "--serve-batch", "4",
        "--network", "wan"])
    assert served == 8.0
