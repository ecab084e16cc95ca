"""Scale-path regressions: the four host-executor determinism/accounting
fixes (exact update budget, seed-dependent server stream, run-start-relative
history, independent per-direction rounding noise) and the sharded
data-parallel trainer's parity with the single-device scan.

The multi-device cases self-adapt: on the tier-1 runner there is exactly 1
CPU device (conftest.py keeps it that way), so they pin BIT-identical
1-device parity; the CI scale job re-runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` which activates the
cross-device equivalence checks.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import PaperLRConfig, VFLConfig
from repro.core import asyrevel, zoo
from repro.core.async_host import HostAsyncTrainer
from repro.core.exchange import ZOExchange
from repro.core.vfl import PaperLRModel, pad_features
from repro.utils.prng import fold_name


def _lr_setup(q=4, d=16, n=128, seed=0):
    model = PaperLRModel(PaperLRConfig(num_features=d, num_parties=q))
    key = jax.random.key(seed)
    X = jax.random.normal(key, (n, d))
    y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (n,)))
    return model, {"x": pad_features(X, d, q), "y": y}


def _host_trainer(model, data, seed=0, **vfl_kw):
    vfl = VFLConfig(num_parties=model.num_parties, mu=1e-3, lr_party=1e-2,
                    lr_server=1e-3, **vfl_kw)
    return HostAsyncTrainer(model, vfl, np.asarray(data["x"]),
                            np.asarray(data["y"]), batch_size=8,
                            compute_cost_s=0.0, seed=seed)


# ------------------------------------------------ budget accounting -------

def test_run_async_spends_exactly_the_update_budget():
    """The budget is CLAIMED under the server lock before a round starts,
    so q racing parties can no longer overshoot by up to q-1 rounds."""
    model, data = _lr_setup()
    for total in (1, 7, 24):
        tr = _host_trainer(model, data)
        res = tr.run_async(total_updates=total)
        assert res.updates == total
        assert len(res.history) == total
        assert res.comms.rounds == total


def test_run_async_budget_exact_with_stragglers():
    model, data = _lr_setup()
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=1e-3)
    tr = HostAsyncTrainer(model, vfl, np.asarray(data["x"]),
                          np.asarray(data["y"]), batch_size=8,
                          compute_cost_s=2e-3, straggler={0: 5.0})
    assert tr.run_async(total_updates=11).updates == 11


# ------------------------------------------- server direction stream ------

def test_server_perturbation_stream_depends_on_trainer_seed():
    """_Server.handle used jax.random.key(updates) — every seed replayed
    the identical server direction sequence. The stream must fold the
    trainer seed: same inputs + different seeds => different w0 update."""
    model, data = _lr_setup()

    def after_one_round(seed):
        from repro.core.wire import SERVER, Message, party
        tr = _host_trainer(model, data, seed=seed)
        tr.server.w0 = {"b": jnp.zeros((), jnp.float32)}  # common start
        idx = np.arange(8)
        c = np.linspace(-1.0, 1.0, 8).astype(np.float32)
        tr.server.handle(
            Message.make("c_up", party(0), SERVER, 0, c,
                         meta={"idx": idx}),
            Message.make("c_hat_up", party(0), SERVER, 0, c + 0.01,
                         meta={"idx": idx}))
        return float(tr.server.w0["b"])

    b0, b0_again, b1 = after_one_round(0), after_one_round(0), \
        after_one_round(1)
    assert b0 == b0_again            # still deterministic per seed
    assert b0 != b1                  # and the seed actually matters


# ----------------------------------------------- run-relative history -----

def test_history_clock_starts_at_run_not_construction():
    """t0 was stamped in __init__, so jit warm-up and setup between
    construction and run_* leaked into every wall-clock figure."""
    model, data = _lr_setup()
    tr = _host_trainer(model, data)
    time.sleep(0.3)                  # stand-in for warm-up between
    #                                  __init__ and the run
    res = tr.run_async(total_updates=5)
    assert res.history[0][0] < 0.25
    assert all(t2 >= t1 for (t1, _), (t2, _) in
               zip(res.history, res.history[1:]))


def test_spent_trainer_refuses_second_run():
    model, data = _lr_setup()
    tr = _host_trainer(model, data)
    tr.run_async(total_updates=3)
    with pytest.raises(RuntimeError):
        tr.run_async(total_updates=3)
    tr2 = _host_trainer(model, data)
    tr2.run_sync(rounds=2)
    with pytest.raises(RuntimeError):
        tr2.run_sync(rounds=2)


# ------------------------------- per-direction stochastic rounding --------

def test_int8_rounding_draws_distinct_across_direction_keys():
    """Each of the K uploads folds its OWN direction subkey into the codec
    key, so the stochastic-rounding draws are independent (a shared draw
    broke the independence behind K-direction variance reduction)."""
    ex = ZOExchange(mu=1e-3, codec="int8", num_directions=4)
    c = jax.random.normal(jax.random.key(9), (64,)) * 2.0
    keys = jax.random.split(jax.random.key(3), 4)
    rts = np.stack([np.asarray(
        ex.roundtrip_up(c, fold_name(k, "codec_hat"))) for k in keys])
    for i in range(4):
        for j in range(i + 1, 4):
            assert (rts[i] != rts[j]).any(), (i, j)


def test_asyrevel_multi_direction_int8_uses_per_direction_codec_keys():
    """Pin the construction end-to-end: the K=2 int8 step equals an
    external reference that quantizes direction i's upload with
    fold_name(k_i, 'codec_hat'), k_i = split(k_u, K)[i]."""
    q, B, K = 4, 8, 2
    model, data = _lr_setup(q=q)
    vfl = VFLConfig(num_parties=q, mu=1e-3, lr_party=1e-2, lr_server=0.0,
                    max_delay=0, perturb_server=False, codec="int8",
                    num_directions=K)
    state = asyrevel.init_state(model, vfl, jax.random.key(0))
    batch = jax.tree.map(lambda a: a[:B], data)
    new_state, h = asyrevel.asyrevel_step(model, vfl, state, batch)

    ex = ZOExchange.from_config(vfl)
    key = jax.random.fold_in(state.key, state.step)
    k_m, k_u, k_c = (fold_name(key, s) for s in ("party", "u", "codec"))
    m_t = int(jax.random.categorical(k_m, jnp.log(jnp.full((q,), 1.0 / q))))
    cs = model.all_party_outputs(state.parties, batch["x"])
    cs = model.map_party_outputs(
        cs, lambda c, m: ex.roundtrip_up(c, jax.random.fold_in(k_c, m)))
    h0 = model.server_forward(state.w0, cs, batch["y"])
    w_m = jax.tree.map(lambda a: a[m_t], state.parties)
    f_base = h0 + vfl.lam * model.regularizer(w_m)

    g = jnp.zeros_like(w_m["w"])
    c_hats = []
    for k_i in jax.random.split(k_u, K):
        w_p, u = zoo.perturb(w_m, k_i, vfl.mu, vfl.direction)
        c_hat = model.party_forward(
            w_p, model.slice_features(batch["x"], m_t), m_t)
        c_hat = ex.roundtrip_up(c_hat, fold_name(k_i, "codec_hat"))
        c_hats.append(np.asarray(c_hat))
        h_bar = model.server_forward(
            state.w0, model.replace_party_output(cs, c_hat, m_t),
            batch["y"])
        coeff = (h_bar + vfl.lam * model.regularizer(w_p) - f_base) / vfl.mu
        g = g + coeff * u["w"] / K
    # the two uploads really carried different rounding noise
    assert (c_hats[0] != c_hats[1]).any()
    np.testing.assert_allclose(
        np.asarray(new_state.parties["w"][m_t]),
        np.asarray(w_m["w"] - vfl.lr_party * g), rtol=1e-5, atol=1e-6)


# ------------------------------------------------- sharded trainer --------

@pytest.mark.slow
@pytest.mark.parametrize("algorithm", ["asyrevel", "synrevel"])
@pytest.mark.parametrize("codec,K", [("f32", 1), ("int8", 2)])
def test_sharded_trainer_bit_identical_on_one_device_mesh(algorithm,
                                                          codec, K):
    """The acceptance invariant: on a 1-device mesh, train_sharded is
    byte-for-byte the single-device scan — same index draws, same
    perturbation keys, pmean over a singleton axis is the identity."""
    model, data = _lr_setup()
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=1e-3,
                    max_delay=2, codec=codec, num_directions=K)
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    s1, l1 = asyrevel.train(model, vfl, data, jax.random.key(5), steps=25,
                            batch_size=8, algorithm=algorithm)
    s2, l2 = asyrevel.train_sharded(model, vfl, data, jax.random.key(5),
                                    steps=25, batch_size=8,
                                    algorithm=algorithm, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(s1.parties["w"]),
                                  np.asarray(s2.parties["w"]))
    np.testing.assert_array_equal(np.asarray(s1.w0["b"]),
                                  np.asarray(s2.w0["b"]))


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device (CI scale job sets "
                           "xla_force_host_platform_device_count=4)")
def test_sharded_trainer_tracks_scan_across_devices():
    """On a dp-device mesh the only numeric difference vs the scan is the
    fp-reassociation of the global batch mean (mean of shard-means), so
    the trajectories must agree to roundoff amplified by 1/mu."""
    dp = jax.device_count()
    model, data = _lr_setup(n=256)
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=1e-3,
                    max_delay=2)
    mesh = jax.make_mesh((dp,), ("data",))
    s1, l1 = asyrevel.train(model, vfl, data, jax.random.key(5), steps=50,
                            batch_size=8 * dp)
    s2, l2 = asyrevel.train_sharded(model, vfl, data, jax.random.key(5),
                                    steps=50, batch_size=8 * dp, mesh=mesh)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-3,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1.parties["w"]),
                               np.asarray(s2.parties["w"]), rtol=2e-2,
                               atol=2e-4)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device (CI scale job sets "
                           "xla_force_host_platform_device_count=4)")
def test_sharded_int8_rounding_independent_per_shard():
    """ShardFoldedExchange folds the data-axis index into the codec key:
    identical per-shard payloads under the replicated step key must NOT
    share one stochastic-rounding draw (the per-direction independence
    fix, applied along the shard axis)."""
    from jax.sharding import PartitionSpec as P

    dp = jax.device_count()
    mesh = jax.make_mesh((dp,), ("data",))
    ex = asyrevel.ShardFoldedExchange(
        ZOExchange(mu=1e-3, codec="int8"), "data")
    c = jax.random.normal(jax.random.key(2), (32,)) * 3.0

    def body(cs):
        return ex.roundtrip_up(cs, jax.random.key(0))

    out = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(
        jnp.tile(c, (dp,)))
    shards = np.asarray(out).reshape(dp, -1)
    for r in range(1, dp):
        assert (shards[0] != shards[r]).any(), r


@pytest.mark.slow
def test_vfl_zoo_step_sharded_matches_unsharded_on_one_device_mesh():
    """launch/steps.py's mesh= path wraps the SAME asyrevel_step in
    shard_map; on a 1-device mesh the two steps must agree exactly."""
    from repro.configs import get_config
    from repro.launch import steps as step_lib
    from repro.models import build_model

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = build_model(cfg)
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-3,
                    lr_server=1e-3 / 4)
    key = jax.random.key(0)
    toks = jax.random.randint(key, (4, 8), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": toks}

    _, init, step = step_lib.make_vfl_zoo_step(model, vfl)
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    _, init_s, step_s = step_lib.make_vfl_zoo_step(model, vfl, mesh=mesh)

    state = init(key)
    s1, h1 = jax.jit(step)(state, batch)
    s2, h2 = jax.jit(step_s)(state, batch)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    for a, b in zip(jax.tree.leaves(s1.parties),
                    jax.tree.leaves(s2.parties)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
