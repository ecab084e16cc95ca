"""The ZOExchange protocol layer: codec round-trip error bounds, measured
vs analytic PRCO agreement, fused-vs-dense update apply, and cross-path
equivalence between the device-scan trainer (asyrevel) and the threaded
host executor (async_host) — both of which route Algorithm 1's message
round through the same core/exchange.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import PaperLRConfig, VFLConfig
from repro.core import asyrevel, comms
from repro.core.exchange import (CommsMeter, ZOExchange, get_codec,
                                 wire_nbytes)
from repro.core.vfl import PaperLRModel, pad_features
from repro.kernels import fused_round
from repro.utils.prng import fold_name


def _lr_setup(q=4, d=16, n=64, seed=0):
    model = PaperLRModel(PaperLRConfig(num_features=d, num_parties=q))
    key = jax.random.key(seed)
    X = jax.random.normal(key, (n, d))
    y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (n,)))
    return model, {"x": pad_features(X, d, q), "y": y}


# ------------------------------------------------------------- codecs -----

def test_f32_codec_is_lossless():
    c = jax.random.normal(jax.random.key(0), (128,))
    np.testing.assert_array_equal(
        np.asarray(get_codec("f32").roundtrip(c)), np.asarray(c))


def test_bf16_codec_relative_error_bound():
    """bf16 keeps 8 significand bits: |rt - c| <= |c| * 2^-8."""
    c = jax.random.normal(jax.random.key(1), (512,)) * 3.0
    rt = np.asarray(get_codec("bf16").roundtrip(c), np.float32)
    assert (np.abs(rt - np.asarray(c))
            <= np.abs(np.asarray(c)) * 2.0 ** -8 + 1e-12).all()


def test_int8_codec_absolute_error_bound_and_unbiased():
    """Stochastic rounding stays within one quantization step of the true
    value and is zero-mean over rounding keys."""
    c = jax.random.normal(jax.random.key(2), (64,)) * 5.0
    codec = get_codec("int8")
    scale = float(jnp.max(jnp.abs(c))) / 127.0
    K = 300
    rts = np.stack([
        np.asarray(codec.roundtrip(c, jax.random.key(k)), np.float32)
        for k in range(K)])
    assert (np.abs(rts - np.asarray(c)[None]) <= scale + 1e-7).all()
    # E[decode(encode(c))] = c: the mean over keys converges at sigma/sqrt(K)
    err = np.abs(rts.mean(0) - np.asarray(c))
    assert err.max() < 0.1 * scale * np.sqrt(300 / K) + 1e-7, err.max()


def test_unknown_codec_rejected():
    with pytest.raises(ValueError):
        get_codec("fp4")


# --------------------------------------------- measured vs analytic -------

@pytest.mark.parametrize("codec", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("K", [1, 3])
def test_measured_round_bytes_agree_with_comms_formulas(codec, K):
    """The shape-derived codec accounting, the REAL encoded-wire sizes,
    and core/comms.py's analytic PRCO formulas must all agree — this is
    the test that stops the four-way drift the exchange layer replaced."""
    B = 64
    c = jax.random.normal(jax.random.key(0), (B,))
    ex = ZOExchange(mu=1e-3, codec=codec, num_directions=K)
    wire = ex.codec.encode(c, jax.random.key(1))
    assert wire_nbytes(wire) == ex.codec.nbytes(c)
    comms.validate_measured(ex.round_comms(c), B, codec=codec,
                            num_directions=K)


def test_bf16_halves_up_bytes():
    c = jnp.zeros((256,))
    up_f32 = ZOExchange(mu=1e-3, codec="f32").round_comms(c).up_bytes
    up_bf16 = ZOExchange(mu=1e-3, codec="bf16").round_comms(c).up_bytes
    assert up_bf16 * 2 == up_f32


def test_meter_accumulates_measured_wire_bytes():
    meter = CommsMeter()
    ex = ZOExchange(mu=1e-3, codec="int8", meter=meter)
    c = jnp.ones((100,))
    ex.encode_up(c)
    ex.encode_up(c)
    ex.send_down(1.0, 2.0)
    assert meter.up_bytes == 2 * (100 + 4)      # int8 values + f32 scale
    assert meter.down_bytes == 8


def test_host_executor_bytes_sourced_from_codec():
    """End-to-end: the host executor's counters are the codec's measured
    payload sizes, and match comms.py per round — for a NON-f32 codec too
    (the old hand-derived accounting could only ever be f32)."""
    from repro.core.async_host import HostAsyncTrainer
    model, data = _lr_setup(n=128)
    B = 16
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=1e-3,
                    codec="bf16")
    tr = HostAsyncTrainer(model, vfl, np.asarray(data["x"]),
                          np.asarray(data["y"]), batch_size=B,
                          compute_cost_s=0.0)
    res = tr.run_async(total_updates=12)
    analytic = comms.zoo_vfl_round(B, codec="bf16")
    assert res.bytes_up == res.updates * analytic.up_bytes
    assert res.bytes_down == res.updates * analytic.down_bytes


# ----------------------------------------------------- update applies -----

def test_fused_apply_matches_seed_replay_rademacher():
    """fused_round.zo_apply (the Pallas zo_update kernel) must be
    bit-compatible with the dense seed-replay path: same per-leaf key
    split, same sign convention."""
    ex = ZOExchange(mu=1e-3, direction="rademacher")
    key = jax.random.key(3)
    w = {"a": jax.random.normal(jax.random.fold_in(key, 1), (300,)),
         "b": jax.random.normal(jax.random.fold_in(key, 2), (7, 5))}
    dense = ex.apply_from_seed(w, key, coeff=2.0, lr=0.1)
    fused = fused_round.zo_apply(w, key, jnp.float32(0.1 * 2.0))
    for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_fused_apply_awkward_leaf_sizes():
    """Leaves whose 256-padded length is not a multiple of 1024 (e.g.
    1100 -> 1280) must still go through the kernel block plumbing."""
    ex = ZOExchange(mu=1e-3, direction="rademacher")
    key = jax.random.key(4)
    for n in (1100, 1025, 257, 3):
        w = {"a": jax.random.normal(key, (n,))}
        dense = ex.apply_from_seed(w, key, coeff=1.0, lr=0.1)
        fused = fused_round.zo_apply(w, key, jnp.float32(0.1 * 1.0))
        np.testing.assert_allclose(np.asarray(dense["a"]),
                                   np.asarray(fused["a"]), atol=1e-6)


def test_rademacher_direction_through_trainer():
    """AsyREVEL runs end-to-end with the fused-kernel direction law."""
    model, data = _lr_setup()
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=1e-3,
                    max_delay=2, direction="rademacher")
    state, losses = asyrevel.train(model, vfl, data, jax.random.key(0),
                                   steps=30, batch_size=8)
    assert np.isfinite(np.asarray(losses)).all()


# ------------------------------------------------- cross-path parity ------

@pytest.mark.parametrize("K", [1, 3])
def test_device_scan_and_host_executor_same_party_update(K):
    """The tentpole invariant: given identical seeds/batches/initial
    state, the jit device-scan trainer and the threaded host executor
    produce the SAME party update, because both route the round through
    the shared ZOExchange (perturb with the same key, same coefficient,
    same apply; for K > 1 the same mean over the directions)."""
    from repro.core.async_host import HostAsyncTrainer
    q, B = 4, 8
    model, data = _lr_setup(q=q)
    vfl = VFLConfig(num_parties=q, mu=1e-3, lr_party=1e-2, lr_server=0.0,
                    max_delay=0, perturb_server=False, num_directions=K)
    state = asyrevel.init_state(model, vfl, jax.random.key(0))
    batch = jax.tree.map(lambda a: a[:B], data)
    new_state, h = asyrevel.asyrevel_step(model, vfl, state, batch)

    # the transcript-visible schedule of the device step: activated party
    # and its direction key
    step_key = jax.random.fold_in(state.key, state.step)
    m_t = int(jax.random.categorical(fold_name(step_key, "party"),
                                     jnp.log(jnp.full((q,), 1.0 / q))))
    k_u = fold_name(step_key, "u")

    tr = HostAsyncTrainer(model, vfl, np.asarray(data["x"]),
                          np.asarray(data["y"]), batch_size=B,
                          compute_cost_s=0.0)
    # identical initial state + a warm c table (max_delay=0 on the device
    # path means the server saw every party's FRESH c for this batch)
    tr.party_w = [jax.tree.map(lambda a, m=m: a[m], state.parties)
                  for m in range(q)]
    tr.server.w0 = state.w0
    idx = np.arange(B)
    cs = model.all_party_outputs(state.parties, batch["x"])
    tr.server.c_table[idx] = np.asarray(cs, np.float32)

    tr.party_step(m_t, idx, k_u)

    # tolerance: the wire carries f32 scalars and the coefficient divides
    # their difference by mu=1e-3, so the two paths agree to f32 roundoff
    # amplified ~1/mu (the host forms the coefficient in python float64,
    # the device in f32)
    np.testing.assert_allclose(
        np.asarray(tr.party_w[m_t]["w"]),
        np.asarray(new_state.parties["w"][m_t]), rtol=5e-4, atol=1e-6)
    # and the untouched blocks stayed identical on both paths
    for m in range(q):
        if m != m_t:
            np.testing.assert_array_equal(
                np.asarray(tr.party_w[m]["w"]),
                np.asarray(new_state.parties["w"][m]))


def test_codec_applies_per_party_message():
    """The device-scan path must quantize each party's upload as its OWN
    message (own absmax scale), like the host executor's wire — a joint
    (B, q) quantization would let one large-magnitude party wipe out the
    int8 resolution of every other party's column."""
    model, _ = _lr_setup(q=4)
    key = jax.random.key(5)
    # party 0 is 1000x larger than the rest
    cs = jax.random.normal(key, (8, 4)) * jnp.array([[1e3, 1.0, 1.0, 1.0]])
    ex = ZOExchange(mu=1e-3, codec="int8")
    out = model.map_party_outputs(
        cs, lambda c, m: ex.roundtrip_up(c, jax.random.fold_in(key, m)))
    # small parties keep per-message resolution: error bounded by their
    # OWN scale, not party 0's
    for m in range(1, 4):
        own_scale = float(jnp.max(jnp.abs(cs[:, m]))) / 127.0
        err = np.abs(np.asarray(out[:, m] - cs[:, m]))
        assert err.max() <= own_scale + 1e-7
    # a joint quantization would have error ~ 1e3/127 ~ 8 on those columns
    joint_scale = float(jnp.max(jnp.abs(cs))) / 127.0
    assert joint_scale > 1.0


# --------------------------------------------------- codec'd training -----

@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_asyrevel_trains_through_lossy_codec(codec):
    """Compressed up-links must still optimize: loss decreases and stays
    finite (the convergence-vs-codec sweep lives in
    benchmarks/bench_communication.py)."""
    model, data = _lr_setup(n=128)
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=5e-2,
                    lr_server=1e-2, max_delay=0, codec=codec)
    state, losses = asyrevel.train(model, vfl, data, jax.random.key(1),
                                   steps=300, batch_size=16)
    losses = np.asarray(losses)
    assert np.isfinite(losses).all()
    assert losses[-50:].mean() < losses[:50].mean()
