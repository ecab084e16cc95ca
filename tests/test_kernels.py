"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.zo_update import zo_update_pallas

KEY = jax.random.key(42)


def _rand(shape, dtype, salt):
    return jax.random.normal(jax.random.fold_in(KEY, salt), shape,
                             jnp.float32).astype(dtype)


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 512, 384),
                                   (128, 1024, 256), (512, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dual_matmul_sweep(M, K, N, dtype):
    x = _rand((M, K), dtype, 1)
    w = _rand((K, N), dtype, 2)
    u = _rand((K, N), jnp.float32, 3)
    y0, y1 = ops.dual_matmul(x, w, u, mu=1e-2, bm=128, bn=128, bk=128)
    r0, r1 = ref.dual_matmul_ref(x, w, u, mu=1e-2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(r0, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(r1, np.float32), atol=tol,
                               rtol=tol)


def test_dual_matmul_perturbation_direction():
    """y1 - y0 must equal mu * x @ u (the two-point numerator)."""
    x = _rand((128, 256), jnp.float32, 4)
    w = _rand((256, 128), jnp.float32, 5)
    u = _rand((256, 128), jnp.float32, 6)
    mu = 1e-3
    y0, y1 = ops.dual_matmul(x, w, u, mu=mu, bm=128, bn=128, bk=128)
    np.testing.assert_allclose(np.asarray(y1 - y0),
                               np.asarray(mu * (x @ u)), atol=1e-4)


@pytest.mark.parametrize("S,hd,bq,bkv", [(128, 64, 64, 64),
                                         (256, 64, 128, 64),
                                         (256, 128, 64, 128),
                                         (512, 32, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(S, hd, bq, bkv, causal):
    B, H, KV = 2, 4, 2
    q = _rand((B, S, H, hd), jnp.float32, 7)
    k = _rand((B, S, KV, hd), jnp.float32, 8)
    v = _rand((B, S, KV, hd), jnp.float32, 9)
    o = ops.flash_attention(q, k, v, causal=causal, bq=bq, bkv=bkv)
    G = H // KV
    o_ref = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        causal=causal).reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtype(dtype):
    B, S, H, hd = 1, 128, 2, 64
    q = _rand((B, S, H, hd), dtype, 10)
    k = _rand((B, S, H, hd), dtype, 11)
    v = _rand((B, S, H, hd), dtype, 12)
    o = ops.flash_attention(q, k, v, causal=True, bq=64, bkv=64)
    o_ref = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        k.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        v.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        causal=True).reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_matches_model_blocked_attention():
    """The kernel and the model's scanning softmax are the same math."""
    from repro.models.attention import blocked_attention
    B, S, H, hd = 2, 256, 4, 64
    q = _rand((B, S, H, hd), jnp.float32, 13)
    k = _rand((B, S, H, hd), jnp.float32, 14)
    v = _rand((B, S, H, hd), jnp.float32, 15)
    o1 = ops.flash_attention(q, k, v, causal=True)
    o2 = blocked_attention(q, k, v, causal=True, kv_block=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("shape", [(1000,), (33, 7), (128, 128),
                                   (4096,), (257,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_zo_update_sweep(shape, dtype):
    w = _rand(shape, dtype, 16)
    bits = jax.random.bits(jax.random.fold_in(KEY, 17), shape, jnp.uint32)
    out = zo_update_pallas(w.reshape(-1), bits.reshape(-1),
                           jnp.float32(0.05)).reshape(shape)
    expect = ref.zo_update_ref(w, bits, jnp.float32(0.05))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=1e-6)


def test_zo_update_is_rademacher_step():
    """Update must move every coordinate by exactly +-scale."""
    w = jnp.zeros((512,), jnp.float32)
    bits = jax.random.bits(jax.random.fold_in(KEY, 18), (512,), jnp.uint32)
    out = zo_update_pallas(w, bits, jnp.float32(0.1))
    np.testing.assert_allclose(np.abs(np.asarray(out)), 0.1, atol=1e-7)
    # roughly balanced signs
    assert 0.3 < float(jnp.mean(out > 0)) < 0.7

# ---------------------------------------------------------------------------
# Fused defended-round kernels (kernels/fused_round + kernels/zo_update):
# every fast path must be BITWISE the unfused eager seam it replaces — the
# unfused code is the oracle, not a reference within tolerance.
# ---------------------------------------------------------------------------
from repro.configs import DPConfig, PaperLRConfig, VFLConfig  # noqa: E402
from repro.core.async_host import HostAsyncTrainer  # noqa: E402
from repro.core.exchange import ZOExchange  # noqa: E402
from repro.core.vfl import PaperLRModel, pad_features  # noqa: E402
from repro.kernels import fused_round, zo_update  # noqa: E402
from repro.utils.prng import sample_direction  # noqa: E402

kernels = pytest.mark.kernels


@kernels
@pytest.mark.parametrize("shape", [(4096,), (33, 7)])
def test_bits_chains_match_jax_random(shape):
    """The bits->sample helpers reproduce jax.random bit-for-bit when fed
    the same uint32 stream those samplers consume internally."""
    key = jax.random.fold_in(KEY, 100)
    bits = jax.random.bits(key, shape, jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(fused_round.uniform_from_bits(bits)),
        np.asarray(jax.random.uniform(key, shape)))
    np.testing.assert_array_equal(
        np.asarray(fused_round.normal_from_bits(bits)),
        np.asarray(jax.random.normal(key, shape)))
    np.testing.assert_array_equal(
        np.asarray(fused_round.laplace_from_bits(bits)),
        np.asarray(jax.random.laplace(key, shape)))
    np.testing.assert_array_equal(
        np.asarray(fused_round.rademacher_from_bits(bits)),
        np.asarray(sample_direction(key, shape, "rademacher")))


@kernels
@pytest.mark.parametrize("N", [3, 257, 1000, 4097])
def test_zo_update_pallas_ragged_n(N):
    """Arbitrary N pads to a block multiple inside; the tail never
    escapes. Bitwise vs the eager unfused chain."""
    w = _rand((N,), jnp.float32, 200 + N)
    bits = jax.random.bits(jax.random.fold_in(KEY, 201), (N,), jnp.uint32)
    out = zo_update.zo_update_pallas(w, bits, jnp.float32(0.03), block=256)
    u = np.where((np.asarray(bits) & 1) == 1, np.float32(1), np.float32(-1))
    expect = np.asarray(w) - np.float32(0.03) * u
    np.testing.assert_array_equal(np.asarray(out), expect)


_DP_BY_MECH = {
    None: None,
    "gaussian": DPConfig(noise_multiplier=1.1, clip=0.7,
                         mechanism="gaussian"),
    "laplace": DPConfig(noise_multiplier=1.1, clip=0.7,
                        mechanism="laplace"),
}


def _ex_pair(codec, dp, K=1):
    mk = lambda fused: ZOExchange.from_config(VFLConfig(  # noqa: E731
        num_parties=2, mu=1e-3, codec=codec, num_directions=K,
        direction="rademacher", dp=dp, fused=fused))
    return mk(False), mk(True)


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@kernels
@pytest.mark.parametrize("codec", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("mech", [None, "gaussian", "laplace"])
def test_defended_encode_xla_and_pallas_match_oracle(codec, mech):
    """fused encode_up (XLA single-dispatch AND the Pallas kernel in
    interpret mode) vs the unfused eager clip->noise->codec chain."""
    ex_u, ex_f = _ex_pair(codec, _DP_BY_MECH[mech])
    c = jax.random.normal(jax.random.fold_in(KEY, 210), (4, 512))
    key = jax.random.fold_in(KEY, 211)
    oracle = ex_u.encode_up(c, key)
    _tree_equal(oracle, fused_round.encode_up_fused(ex_f, c, key,
                                                    impl="xla"))
    _tree_equal(oracle, fused_round.encode_up_fused(ex_f, c, key,
                                                    impl="pallas"))


@kernels
@pytest.mark.parametrize("codec", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("dp_on", [False, True])
@pytest.mark.parametrize("K", [1, 3])
def test_exchange_fused_ops_bitwise(codec, dp_on, K):
    """The full fused surface of ZOExchange vs its unfused oracle:
    encode_up / defend / roundtrip_up / perturb / apply_direction /
    apply_from_seed / party_gradient, every codec x DP x K."""
    dp = _DP_BY_MECH["gaussian"] if dp_on else None
    ex_u, ex_f = _ex_pair(codec, dp, K=K)
    key = jax.random.fold_in(KEY, 220)
    c = jax.random.normal(jax.random.fold_in(KEY, 221), (64,))
    _tree_equal(ex_u.encode_up(c, key), ex_f.encode_up(c, key))
    _tree_equal(ex_u.defend(c, key), ex_f.defend(c, key))
    _tree_equal(ex_u.roundtrip_up(c, key), ex_f.roundtrip_up(c, key))

    w = {"a": jax.random.normal(jax.random.fold_in(KEY, 222), (130,)),
         "b": jax.random.normal(jax.random.fold_in(KEY, 223), (7, 5))}
    p_u, u_u = ex_u.perturb(w, key)
    p_f, u_f = ex_f.perturb(w, key)
    _tree_equal(p_u, p_f)
    _tree_equal(u_u, u_f)
    coeff = jnp.float32(0.37)
    _tree_equal(ex_u.apply_direction(w, u_u, coeff, 1e-2),
                ex_f.apply_direction(w, u_f, coeff, 1e-2))
    _tree_equal(ex_u.apply_from_seed(w, key, coeff, 1e-2),
                ex_f.apply_from_seed(w, key, coeff, 1e-2))

    f_of = lambda w_p, k: 0.1 * sum(  # noqa: E731
        jnp.sum(leaf) for leaf in jax.tree.leaves(w_p))
    _tree_equal(ex_u.party_gradient(w, key, jnp.float32(0.5), f_of),
                ex_f.party_gradient(w, key, jnp.float32(0.5), f_of))


@kernels
@pytest.mark.parametrize("K", [1, 3])
def test_fused_serial_run_bitwise_int8_dp(K):
    """End-to-end: a defended int8 serial run with fused=True reproduces
    the unfused run exactly — losses AND final party blocks (this drives
    the one-dispatch _party_release_jit path in core/async_host)."""
    q, d, n = 2, 16, 64
    model = PaperLRModel(PaperLRConfig(num_features=d, num_parties=q))
    key = jax.random.key(5)
    X = np.asarray(pad_features(jax.random.normal(key, (n, d)), d, q))
    y = np.asarray(jnp.sign(jax.random.normal(
        jax.random.fold_in(key, 1), (n,))))
    dp = DPConfig(noise_multiplier=1.3, clip=1.0)

    def run(fused):
        vfl = VFLConfig(num_parties=q, mu=5e-2, lr_party=1e-2,
                        lr_server=1e-3, codec="int8", num_directions=K,
                        direction="rademacher", dp=dp, fused=fused)
        tr = HostAsyncTrainer(model, vfl, X, y, batch_size=8,
                              compute_cost_s=0.0, seed=0)
        res = tr.run_serial(6)
        return tr, res

    tr_u, res_u = run(False)
    tr_f, res_f = run(True)
    assert [h for _, h in res_u.history] == [h for _, h in res_f.history]
    for m in range(q):
        _tree_equal(tr_u.party_w[m], tr_f.party_w[m])
    assert res_u.bytes_up == res_f.bytes_up
    assert res_u.bytes_down == res_f.bytes_down


@pytest.mark.runtime
@pytest.mark.slow
@kernels
def test_fused_defended_tcp_run_bit_identical_to_memory_reference():
    """The PR-4/PR-5 transport-parity acceptance with the fused fast path
    on: a DP-defended federation over real OS processes/TCP reproduces
    the fused in-memory reference exactly."""
    from repro.configs.base import RuntimeConfig
    from repro.runtime import (history_losses, run_federation,
                               run_reference)
    spec = {"kind": "lr", "parties": 2, "features": 16, "samples": 64,
            "batch": 8, "seed": 0,
            "vfl": {"mu": 5e-2, "lr_party": 1e-2, "lr_server": 1e-3,
                    "direction": "rademacher", "fused": True,
                    "dp": {"epsilon": 10.0, "delta": 1e-5, "clip": 1.0}}}
    res = run_federation(spec, 4, cfg=RuntimeConfig(deadline_s=120.0))
    tr, ref_res = run_reference(spec, 4)
    np.testing.assert_array_equal(
        history_losses(res), np.asarray([h for _, h in ref_res.history]))
    for m in range(2):
        np.testing.assert_array_equal(
            res["parties"][m]["final_w"]["w"],
            np.asarray(tr.party_w[m]["w"]))
