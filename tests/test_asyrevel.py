"""AsyREVEL trainer mechanics: staleness buffer, block-coordinate updates,
activation probabilities (Assumptions 3-4)."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import PaperLRConfig, VFLConfig
from repro.core import asyrevel
from repro.core.vfl import PaperLRModel, TransformerVFLModel, pad_features


def _setup(q=4, d=16, n=64, seed=0):
    model = PaperLRModel(PaperLRConfig(num_features=d, num_parties=q))
    key = jax.random.key(seed)
    X = jax.random.normal(key, (n, d))
    y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (n,)))
    data = {"x": pad_features(X, d, q), "y": y}
    return model, data


def test_single_step_updates_one_party_block_only():
    model, data = _setup()
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2,
                    lr_server=1e-3, max_delay=2)
    state = asyrevel.init_state(model, vfl, jax.random.key(0))
    batch = jax.tree.map(lambda a: a[:8], data)
    new_state, h = asyrevel.asyrevel_step(model, vfl, state, batch)
    diff = np.asarray(jnp.sum(jnp.abs(
        new_state.parties["w"] - state.parties["w"]), axis=-1))
    assert (diff > 0).sum() == 1          # exactly one party moved
    assert np.isfinite(float(h))


def test_history_buffer_tracks_updates():
    """After each step, hist[step % (tau+1)] holds the new party params."""
    model, data = _setup()
    tau = 3
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2,
                    lr_server=1e-3, max_delay=tau)
    state = asyrevel.init_state(model, vfl, jax.random.key(0))
    batch = jax.tree.map(lambda a: a[:8], data)
    for t in range(5):
        new_state, _ = asyrevel.asyrevel_step(model, vfl, state, batch)
        slot = t % (tau + 1)
        np.testing.assert_array_equal(
            np.asarray(new_state.hist["w"][slot]),
            np.asarray(new_state.parties["w"]))
        state = new_state


def test_activation_probabilities_respected():
    """Assumption 3: party m activates with probability p_m."""
    model, data = _setup()
    probs = (0.7, 0.1, 0.1, 0.1)
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=0.0,
                    max_delay=0, activation_probs=probs,
                    perturb_server=False)
    state, losses = asyrevel.train(model, vfl, data, jax.random.key(3),
                                   steps=800, batch_size=8)
    # party 0 should have moved far more than the others
    move = np.asarray(jnp.sum(jnp.abs(state.parties["w"]), axis=-1))
    assert move[0] > move[1:].max()


def test_delay_zero_uses_fresh_params():
    """With tau=0 the stale c's equal fresh c's -> the server loss h equals
    the true current loss of the system."""
    model, data = _setup()
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=1e-3,
                    max_delay=0)
    state = asyrevel.init_state(model, vfl, jax.random.key(0))
    batch = jax.tree.map(lambda a: a[:8], data)
    _, h = asyrevel.asyrevel_step(model, vfl, state, batch)
    cs = model.all_party_outputs(state.parties, batch["x"])
    expect = model.server_forward(state.w0, cs, batch["y"])
    np.testing.assert_allclose(float(h), float(expect), rtol=1e-6)


def test_seed_determinism():
    model, data = _setup()
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=1e-3,
                    max_delay=2)
    s1, l1 = asyrevel.train(model, vfl, data, jax.random.key(5), steps=50,
                            batch_size=8)
    s2, l2 = asyrevel.train(model, vfl, data, jax.random.key(5), steps=50,
                            batch_size=8)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(s1.parties["w"]),
                                  np.asarray(s2.parties["w"]))


def test_only_function_values_cross_boundary():
    """Structural privacy check: the quantities the server consumes from a
    party are exactly (c, c_hat); what the party consumes back is (h,
    h_bar) — scalars. We assert the step function computes the party update
    from scalars + party-local state only, by reproducing it externally."""
    from repro.core import zoo
    from repro.utils.prng import fold_name
    model, data = _setup()
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2, lr_server=0.0,
                    max_delay=0, perturb_server=False)
    state = asyrevel.init_state(model, vfl, jax.random.key(0))
    batch = jax.tree.map(lambda a: a[:8], data)
    new_state, h = asyrevel.asyrevel_step(model, vfl, state, batch)

    # adversary-visible transcript: c's, c_hat, h, h_bar — rebuild update
    key = jax.random.fold_in(state.key, state.step)
    k_m, k_u = fold_name(key, "party"), fold_name(key, "u")
    m_t = int(jax.random.categorical(k_m, jnp.log(jnp.full((4,), 0.25))))
    w_m = jax.tree.map(lambda a: a[m_t], state.parties)
    w_p, u = zoo.perturb(w_m, k_u, vfl.mu, vfl.direction)
    cs = model.all_party_outputs(state.parties, batch["x"])
    c_hat = model.party_forward(w_p, model.slice_features(batch["x"], m_t),
                                m_t)
    h0 = model.server_forward(state.w0, cs, batch["y"])
    h_bar = model.server_forward(
        state.w0, model.replace_party_output(cs, c_hat, m_t), batch["y"])
    coeff = ((h_bar + vfl.lam * model.regularizer(w_p))
             - (h0 + vfl.lam * model.regularizer(w_m))) / vfl.mu
    expect = w_m["w"] - vfl.lr_party * coeff * u["w"]
    np.testing.assert_allclose(np.asarray(new_state.parties["w"][m_t]),
                               np.asarray(expect), rtol=1e-5, atol=1e-6)


# ------------------------------------------ transformer stale read ------

class _WholeTableRead(TransformerVFLModel):
    """The stale read written the whole-table way: every party's stale
    params gathered out of the ring buffer, vocab x dq table included,
    then the towers run on them."""

    def stale_party_outputs(self, hist, slots, tokens):
        q = slots.shape[0]
        stale = jax.tree.map(lambda h: h[slots, jnp.arange(q)], hist)
        return self.all_party_outputs(stale, tokens)


def _zoo_models(q=4, tau=4):
    from repro.configs import get_config
    from repro.models import build_model
    vfl = VFLConfig(num_parties=q, party_hidden=16, max_delay=tau,
                    mu=1e-3, lr_party=1e-3, lr_server=1e-3 / q)
    model = build_model(get_config("qwen1.5-0.5b", reduced=True))
    return TransformerVFLModel(model, vfl), _WholeTableRead(model, vfl), vfl


@pytest.mark.parametrize("slots", ["equal", "distinct", "random"])
def test_transformer_stale_rows_match_whole_table_read(slots):
    """Reading the batch's embedding rows straight out of the ring buffer
    gives the whole-table read's c's bit for bit."""
    vm, ref, vfl = _zoo_models()
    q, n = vfl.num_parties, vfl.max_delay + 1
    keys = jax.random.split(jax.random.key(0), n + 2)
    hist = jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[vm.init_parties_stacked(k) for k in keys[:n]])
    slots = {"equal": jnp.full((q,), 2),
             "distinct": jnp.array([3, 0, 4, 1]),
             "random": jax.random.randint(keys[n], (q,), 0, n)}[slots]
    toks = jax.random.randint(keys[n + 1], (2, 8), 0,
                              vm.model.cfg.vocab_size)
    got = jax.jit(vm.stale_party_outputs)(hist, slots, toks)
    want = jax.jit(ref.stale_party_outputs)(hist, slots, toks)
    assert got.shape == (2, 8, q, vm.dq)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mesh", [False, True])
def test_transformer_steps_match_whole_table_read(mesh):
    """AsyREVEL rounds on the row read leave every AsyState leaf and
    every h bitwise where the whole-table read leaves them, in the scan
    and on a 1-device mesh; 8 rounds at tau = 4 fill every slot."""
    vm, ref, vfl = _zoo_models()
    toks = jax.random.randint(jax.random.key(1), (16, 8), 0,
                              vm.model.cfg.vocab_size)
    data = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    run = asyrevel.train
    if mesh:
        run = functools.partial(asyrevel.train_sharded, mesh=jax.make_mesh(
            (1,), ("data",), devices=jax.devices()[:1]))
    s1, h1 = run(vm, vfl, data, jax.random.key(2), steps=8, batch_size=4)
    s2, h2 = run(ref, vfl, data, jax.random.key(2), steps=8, batch_size=4)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    s1, s2 = (s._replace(key=jax.random.key_data(s.key)) for s in (s1, s2))
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _whole_table_gathers(lowered_text, table_shape):
    """StableHLO gathers that read a (tau+1, q, V, dq) table at V rows."""
    table = "tensor<{}xf32>".format("x".join(map(str, table_shape)))
    out = []
    for line in lowered_text.splitlines():
        if "stablehlo.gather" not in line:
            continue
        operand = re.search(r"\) <\{.*\}> : \((tensor<[^>]*>)", line)
        sizes = re.search(r"slice_sizes = array<i64: ([0-9, ]+)>", line)
        if (operand.group(1) == table
                and int(sizes.group(1).split(",")[2]) == table_shape[2]):
            out.append(line)
    return out


def test_zoo_step_reads_no_whole_party_table():
    """The lowered vfl-zoo step gathers no whole vocab x dq slice out of
    the ring buffer; the whole-table read, lowered the same way, does."""
    from repro.launch import steps as step_lib
    vm, ref, vfl = _zoo_models()
    _, init, step = step_lib.make_vfl_zoo_step(vm.model, vfl)
    state = jax.eval_shape(init, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 8), jnp.int32)
             for k in ("tokens", "targets")}
    shape = state.hist["embed"].shape
    got = jax.jit(step).lower(state, batch).as_text()
    assert "stablehlo.gather" in got
    assert _whole_table_gathers(got, shape) == []
    control = jax.jit(functools.partial(asyrevel.asyrevel_step, ref, vfl)
                      ).lower(state, batch).as_text()
    assert _whole_table_gathers(control, shape)
