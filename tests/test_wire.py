"""The wire subsystem: typed messages, channel accounting/clocks, transcript
recording + replay determinism, the InMemoryChannel bit-identity regression
against the pre-wire executor, and the transcript-driven privacy attacks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import NETWORK_PROFILES, PaperLRConfig, VFLConfig
from repro.core import comms, privacy, wire
from repro.core.async_host import HostAsyncTrainer
from repro.core.tig import BlackBoxError, HostTIGTrainer
from repro.core.vfl import PaperLRModel, pad_features
from repro.core.wire import (SERVER, InMemoryChannel, Message,
                             NetworkChannel, RecordingChannel,
                             ReplayChannel, Transcript, party)


def _lr_setup(q=4, d=16, n=128, seed=0):
    model = PaperLRModel(PaperLRConfig(num_features=d, num_parties=q))
    key = jax.random.key(seed)
    X = jax.random.normal(key, (n, d))
    y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (n,)))
    return model, pad_features(X, d, q), np.asarray(y)


def _trainer(codec="f32", K=1, channel=None, seed=0, q=4, batch=8):
    model, X, y = _lr_setup(q=q)
    vfl = VFLConfig(num_parties=q, mu=1e-3, lr_party=1e-2, lr_server=1e-3,
                    codec=codec, num_directions=K)
    return HostAsyncTrainer(model, vfl, np.asarray(X), y, batch_size=batch,
                            compute_cost_s=0.0, seed=seed, channel=channel)


# ------------------------------------------------------------- messages ---

def test_message_kind_validated():
    with pytest.raises(ValueError):
        Message.make("grad_up", party(0), SERVER, 0, np.zeros(3))


def test_message_nbytes_measured_from_payload():
    msg = Message.make("c_up", party(1), SERVER, 0,
                       np.zeros((8,), np.float32))
    assert msg.nbytes == 32
    # int8 wire tuple: values + f32 scale
    msg = Message.make("c_up", party(1), SERVER, 0,
                       (np.zeros((8,), np.int8), np.float32(1.0)))
    assert msg.nbytes == 12
    # loss_down scalars are f32 on the wire regardless of python floats
    msg = Message.make("loss_down", SERVER, party(1), 0, (0.1, 0.2, 0.3))
    assert msg.nbytes == 12


def test_party_endpoint_roundtrip():
    assert wire.party_index(party(3)) == 3
    with pytest.raises(ValueError):
        wire.party_index(SERVER)


# ------------------------------------------- runtime wire codec (PR 4) ----

def _codec_payload(codec, shape=(8,), key=None):
    """A realistic encoded up-link payload for each codec."""
    from repro.core.exchange import get_codec
    c = jnp.arange(1, 1 + int(np.prod(shape)),
                   dtype=jnp.float32).reshape(shape) / 7.0
    wire = get_codec(codec).encode(c, key)
    return jax.tree.map(np.asarray, wire)


@pytest.mark.parametrize("codec", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("kind", list(wire.KINDS))
def test_wire_codec_roundtrip_every_kind_and_codec(codec, kind):
    """Satellite: every Message kind x payload codec encodes/decodes
    byte-identically through the runtime's versioned wire codec, and the
    decoded nbytes equals wire_nbytes of the original payload."""
    from repro.core.exchange import wire_nbytes
    from repro.runtime.transport import decode_message, encode_message
    if kind == "loss_down":
        payload = (0.5, 0.25, float(np.float32(1 / 3)))
    elif kind in ("grad_down", "param_down"):
        payload = np.linspace(-1, 1, 12, dtype=np.float32)
    else:
        payload = _codec_payload(codec, key=jax.random.key(0))
    sender, receiver = ((party(1), SERVER) if kind in wire.UP_KINDS
                        else (SERVER, party(1)))
    msg = Message.make(kind, sender, receiver, 9, payload,
                       meta={"idx": np.arange(4), "dir": 2})
    buf = encode_message(msg)
    assert encode_message(msg) == buf            # deterministic bytes
    got = decode_message(buf)
    assert (got.kind, got.sender, got.receiver, got.round) == \
        (kind, sender, receiver, 9)
    assert got.nbytes == msg.nbytes
    if kind == "loss_down":
        assert got.nbytes == 3 * 4
        assert got.scalars() == msg.scalars()    # f32-exact scalars
    else:
        assert got.nbytes == wire_nbytes(payload)
        la = [np.asarray(x) for x in jax.tree.leaves(payload)]
        lb = [np.asarray(x) for x in jax.tree.leaves(got.payload)]
        for a, b in zip(la, lb):
            assert a.dtype == b.dtype
            assert a.tobytes() == b.tobytes()    # byte-identical
    np.testing.assert_array_equal(got.meta["idx"], msg.meta["idx"])
    assert got.meta["dir"] == 2


def test_wire_codec_rejects_nbytes_mismatch():
    """The measured-bytes contract is VALIDATED at the socket: a message
    whose declared nbytes disagrees with the payload bytes that would
    hit the wire refuses to encode, and a tampered frame refuses to
    decode."""
    from repro.runtime.transport import (WireFormatError, decode_message,
                                         encode_message)
    bad = Message("c_up", party(0), SERVER, 0, np.zeros(4, np.float32),
                  nbytes=99, meta=None)
    with pytest.raises(WireFormatError):
        encode_message(bad)
    good = encode_message(Message.make(
        "c_up", party(0), SERVER, 0, np.zeros(4, np.float32)))
    with pytest.raises(WireFormatError):
        decode_message(b"XX" + good[2:])         # bad magic
    with pytest.raises(WireFormatError):
        decode_message(good[:2] + b"\x07" + good[3:])   # bad version


# ----------------------------------------------------------- transcript ---

def test_transcript_views_are_what_each_endpoint_observes():
    t = Transcript()
    for rnd in range(2):
        for m in (0, 1):
            t.append(Message.make("c_up", party(m), SERVER, rnd,
                                  np.zeros(4, np.float32)))
            t.append(Message.make("loss_down", SERVER, party(m), rnd,
                                  (0.5, 0.6)))
    # a curious party sees only its own links — 4 of the 8 messages
    v0 = t.view(party(0))
    assert len(v0) == 4
    assert all(party(0) in (m.sender, m.receiver) for m in v0)
    # the server sees everything here (it is on every link)
    assert len(t.view(SERVER)) == 8
    # colluding parties pool views without duplicating shared messages
    assert len(t.pooled_view([party(0), party(1)])) == 8
    assert t.kinds() == {"c_up", "loss_down"}
    assert t.bytes_by_kind() == {"c_up": 4 * 16, "loss_down": 4 * 8}


# ------------------------------------------- bit-identity regression ------

# The wire layer is transport, not math. The deterministic serial
# schedule (6 rounds x 4 parties, _lr_setup data, batch 8, seed 0) over
# the InMemoryChannel must reproduce bit for bit the same schedule over a
# second channel kind — a NetworkChannel pricing every message on the WAN
# profile's jittered virtual clock — and both must carry the analytic
# per-direction byte counts: 6*4 rounds x 2 uploads x batch 8 x 4 B (f32)
# or x (8 + 4) B (int8 values + scale), and 6*4 x 2 f32 losses down.
_WIRE_BYTES = {"f32": (1536, 192), "int8": (576, 192)}


@pytest.mark.parametrize("codec", ["f32", "int8"])
def test_inmemory_channel_bit_identical_to_prewire_executor(codec):
    tr = _trainer(codec=codec)
    res = tr.run_serial(rounds=6)
    net = NetworkChannel(NETWORK_PROFILES["wan"], seed=3)
    ref = _trainer(codec=codec, channel=net)
    ref_res = ref.run_serial(rounds=6)
    assert net.time_s > 0                         # the clock really ran
    for a, b in zip(tr.party_w, ref.party_w):
        np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))
    np.testing.assert_array_equal(np.asarray(tr.server.w0["b"]),
                                  np.asarray(ref.server.w0["b"]))
    assert [h for _, h in res.history] == [h for _, h in ref_res.history]
    assert (res.bytes_up, res.bytes_down) == _WIRE_BYTES[codec]
    assert (ref_res.bytes_up, ref_res.bytes_down) == _WIRE_BYTES[codec]
    # and the schedule trained: every party block moved off its init
    init = _trainer(codec=codec)
    for a, b in zip(tr.party_w, init.party_w):
        assert not np.array_equal(np.asarray(a["w"]), np.asarray(b["w"]))


# -------------------------------------------------- record + replay -------

def test_recording_run_and_replay_bitwise_identical():
    """Wire-layer determinism: a recorded run and its replay (same seed,
    ReplayChannel verifying every message against the transcript) produce
    bitwise-identical party/server params and byte counts."""
    rec = RecordingChannel()
    tr1 = _trainer(codec="int8", channel=rec)
    res1 = tr1.run_serial(rounds=4)

    rep = ReplayChannel(rec.transcript)
    tr2 = _trainer(codec="int8", channel=rep)
    res2 = tr2.run_serial(rounds=4)

    assert rep.exhausted()
    for a, b in zip(tr1.party_w, tr2.party_w):
        np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))
    np.testing.assert_array_equal(np.asarray(tr1.server.w0["b"]),
                                  np.asarray(tr2.server.w0["b"]))
    assert (res1.bytes_up, res1.bytes_down) == \
        (res2.bytes_up, res2.bytes_down)
    assert rep.bytes_by_kind == rec.transcript.bytes_by_kind()


def test_replay_detects_divergent_traffic():
    rec = RecordingChannel()
    tr1 = _trainer(channel=rec)
    tr1.run_serial(rounds=2)
    rep = ReplayChannel(rec.transcript)
    tr2 = _trainer(channel=rep, seed=1)      # different seed -> different
    with pytest.raises(AssertionError):      # batches/payloads on the wire
        tr2.run_serial(rounds=2)


# ------------------------------------------------- channel accounting -----

@pytest.mark.parametrize("codec", ["f32", "bf16", "int8"])
def test_network_channel_accounting_agrees_with_meter(codec):
    """The three-way byte agreement for every codec: channel per-kind
    counters == exchange CommsMeter == analytic PRCO."""
    ch = NetworkChannel(NETWORK_PROFILES["lan"])
    tr = _trainer(codec=codec, channel=ch)
    res = tr.run_serial(rounds=3)
    assert ch.up_bytes == res.bytes_up
    assert ch.down_bytes == res.bytes_down
    comms.validate_channel(ch, res.updates, batch=8, codec=codec)
    comms.validate_measured(
        comms.RoundComms(ch.up_bytes // res.updates,
                         ch.down_bytes // res.updates), 8, codec=codec)
    assert ch.time_s > 0


def test_host_k_directions_down_link_accounting():
    """K>1 on the host executor: (1+K) up-link payloads and (1+K) down
    scalars per round, agreeing across channel, meter, and analytic."""
    K = 3
    ch = NetworkChannel(NETWORK_PROFILES["lan"])
    tr = _trainer(K=K, channel=ch)
    res = tr.run_serial(rounds=2)
    an = comms.zoo_vfl_round(8, codec="f32", num_directions=K)
    assert res.bytes_down == res.updates * an.down_bytes == \
        res.updates * (1 + K) * 4
    assert res.bytes_up == res.updates * an.up_bytes
    comms.validate_channel(ch, res.updates, batch=8, num_directions=K)
    assert ch.msgs_by_kind["c_hat_up"] == K * res.updates
    losses = [h for _, h in res.history]
    assert np.isfinite(losses).all()


def test_network_clock_prices_messages():
    cfg = NETWORK_PROFILES["lan"]
    ch = NetworkChannel(cfg)
    msg = Message.make("c_up", party(0), SERVER, 0,
                       np.zeros(1000, np.float32))
    ch.send(msg)
    expect = cfg.latency_s + 4000 / cfg.bandwidth_Bps
    assert ch.time_s == pytest.approx(expect)
    # straggler profile: party 0's link pays the multiplier, party 1's not
    ch2 = NetworkChannel(NETWORK_PROFILES["straggler"])
    ch2.send(Message.make("c_up", party(0), SERVER, 0,
                          np.zeros(1000, np.float32)))
    t0 = ch2.time_s
    ch2.send(Message.make("c_up", party(1), SERVER, 0,
                          np.zeros(1000, np.float32)))
    assert t0 == pytest.approx(6.0 * (ch2.time_s - t0))


def test_network_jitter_deterministic_per_seed():
    cfg = NETWORK_PROFILES["wan"]
    def clock(seed):
        ch = NetworkChannel(cfg, seed=seed)
        for r in range(5):
            ch.send(Message.make("c_up", party(0), SERVER, r,
                                 np.zeros(64, np.float32)))
        return ch.time_s
    assert clock(0) == clock(0)
    assert clock(0) != clock(1)


def test_measured_table3_ratio_within_5pct_of_analytic():
    """Acceptance: paper_ratio reproduced by measured channel time."""
    for d_l in (12, 16, 37, 98, 250, 5904):
        analytic = comms.paper_ratio(d_l, batch=1)
        measured = comms.measured_paper_ratio(d_l, batch=1)
        assert abs(measured - analytic) / analytic < 0.05, d_l


# --------------------------------------- transcripts drive the attacks ----

def _recorded_pair(rounds=10, batch=16):
    """Same data + seed through both frameworks; two transcripts."""
    model, X, y = _lr_setup(d=32, n=128)
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=5e-2,
                    lr_server=1e-2 / 4)
    rec_zoo, rec_tig = RecordingChannel(), RecordingChannel()
    HostAsyncTrainer(model, vfl, np.asarray(X), y, batch_size=batch,
                     compute_cost_s=0.0, seed=0,
                     channel=rec_zoo).run_serial(rounds=rounds)
    HostTIGTrainer(model, vfl, np.asarray(X), y, batch_size=batch, seed=0,
                   channel=rec_tig, sampler="full").run(rounds=rounds)
    return rec_zoo.transcript, rec_tig.transcript, y


def test_label_inference_from_recorded_transcripts():
    """The paper's Table-1 label-inference row, measured from executor
    traffic: ~1.0 accuracy off TIG's grad_down, ~chance off ZOO-VFL's
    loss_down — same data, same seeds."""
    t_zoo, t_tig, y = _recorded_pair()
    tig = privacy.label_inference_attack(t_tig, y, m=0)
    zoo = privacy.label_inference_attack(t_zoo, y, m=0)
    assert tig["observable"] == "grad_down"
    assert tig["accuracy"] == 1.0
    assert zoo["observable"] == "loss_down"
    assert abs(zoo["accuracy"] - 0.5) < 0.1


def test_rma_needs_grad_on_the_wire():
    t_zoo, t_tig, _ = _recorded_pair(rounds=4)
    rma_tig = privacy.reverse_multiplication_from_transcript(
        t_tig, eta=5e-2, colluders=(0, 1))
    assert rma_tig["feasible"] and rma_tig["recovered"] is not None
    rma_zoo = privacy.reverse_multiplication_from_transcript(
        t_zoo, eta=5e-2, colluders=(0, 1))
    assert not rma_zoo["feasible"] and rma_zoo["recovered"] is None


def test_feature_inference_underdetermined_without_param_down():
    t_zoo, _, _ = _recorded_pair(rounds=4)
    fi = privacy.feature_inference_from_transcript(t_zoo, x_dim=8)
    assert not fi["params_leaked"]
    assert fi["ratio"] < 1.0 and not fi["solvable"]


def test_replay_backdoor_direction_control_by_observable():
    t_zoo, t_tig, _ = _recorded_pair(rounds=4)
    bd_tig = privacy.replay_backdoor_attack(t_tig, lr=5e-2, mu=1e-3,
                                            w_dim=4096)
    assert bd_tig["direction_control"]
    cos = np.mean([privacy.replay_backdoor_attack(
        t_zoo, lr=5e-2, mu=1e-3, w_dim=4096,
        key=jax.random.key(s))["cos_to_target"] for s in range(10)])
    assert cos < 0.05


def test_exposure_derived_from_observed_kinds():
    t_zoo, t_tig, _ = _recorded_pair(rounds=2)
    ex_zoo = privacy.exposure_from_transcript(t_zoo)
    assert not ex_zoo["intermediate_grads"] and not ex_zoo["model_params"]
    assert ex_zoo["function_values"]
    ex_tig = privacy.exposure_from_transcript(t_tig)
    assert ex_tig["intermediate_grads"] and not ex_tig["model_params"]


# ------------------------------------------------------ TIG host executor -

def test_host_tig_trainer_trains_and_refuses_black_box():
    model, X, y = _lr_setup(d=32, n=128)
    vfl = VFLConfig(num_parties=4, lr_party=5e-2, lr_server=1e-2)
    tr = HostTIGTrainer(model, vfl, np.asarray(X), y, batch_size=32,
                        seed=0)
    hist = tr.run(rounds=20)
    assert hist[-1] < hist[0]
    assert np.isfinite(hist).all()
    with pytest.raises(BlackBoxError):
        HostTIGTrainer(model, vfl, np.asarray(X), y, black_box=True)


def test_host_tig_byte_accounting_matches_tig_round():
    model, X, y = _lr_setup()
    vfl = VFLConfig(num_parties=4)
    ch = InMemoryChannel()
    tr = HostTIGTrainer(model, vfl, np.asarray(X), y, batch_size=16,
                        seed=0, channel=ch)
    tr.run(rounds=3)
    rounds = 3 * 4
    an = comms.tig_round(batch=16)
    assert ch.bytes_by_kind["c_up"] == rounds * an.up_bytes
    assert ch.bytes_by_kind["grad_down"] == rounds * an.down_bytes
    # + the 4-byte monitoring loss scalar per round
    assert ch.bytes_by_kind["loss_down"] == rounds * 4


def test_tig_step_respects_activation_probs():
    """Satellite: tig_step must sample the activated party from
    vfl.activation_probs (shared with AsyREVEL), not uniformly — with a
    point mass on party 0, the other parties' blocks never move."""
    from repro.core.tig import tig_train
    model, X, y = _lr_setup()
    data = {"x": X, "y": jnp.asarray(y)}
    vfl = VFLConfig(num_parties=4, lr_party=5e-2, lr_server=1e-2,
                    activation_probs=(1.0, 0.0, 0.0, 0.0))
    state, losses = tig_train(model, vfl, data, jax.random.key(0),
                              steps=20, batch_size=8)
    w = np.asarray(state.parties["w"])
    assert np.abs(w[0]).max() > 0            # party 0 trained
    np.testing.assert_array_equal(w[1:], 0)  # others never activated
