"""Bring-up check: the AsyREVEL stack's main paths on a TPU.

  python chip_smoke.py             # phases a-e on one chip
  python chip_smoke.py --chips 4   # only the sharded --data-parallel 4
                                   # trainer against a 1-device mesh

One process drives every phase (a chip belongs to one process at a
time), through the entry points a user calls:

  a. device report; anything but a TPU exits non-zero here;
  b. the full-width launcher step (qwen1.5-0.5b at its published widths,
     repro.launch.train's vfl-zoo path), then its defended variant;
  c. the reduced step on the chip against the same step on the host CPU;
  d. the paper's scan trainer on its LR model at D4 a9a shapes;
  e. every Pallas kernel compiled for the chip against its reference.

Timings printed here are smoke timings of one run, not benchmark
numbers. Any failed check raises and the script exits non-zero; the
last stdout line, printed only when every phase passed, is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import (DPConfig, PaperLRConfig, VFLConfig,  # noqa: E402
                           get_config)
from repro.launch import train  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

TRAIN_ARGV = ["--arch", "qwen1.5-0.5b", "--mode", "vfl-zoo",
              "--parties", "4", "--steps", "8", "--batch-size", "8",
              "--seq-len", "64"]
# the defended variant docs/kernels.md and the README document
DEFENDED_ARGV = ["--fused", "--codec", "int8", "--dp-epsilon", "8",
                 "--dp-clip", "1.0"]
# random-token targets put step 0's cross-entropy at ln V plus half the
# variance of the random-init logits (about 0.06 nats at the reduced
# width, where V = 512). One nat keeps that margin with room to spare
# and still tells V = 151,936 (ln V = 11.93) from any cut vocabulary.
H0_TOL_NATS = 1.0
# Phases c and --chips 4 hold a run to a reference run by the relative
# loss difference (at step 0, before any update, and the largest over
# the steps) and by the "update gap", per block (w0, each party's)
# ||change - reference change|| / ||reference change||, where a change
# is final minus initial parameters. The losses alone cannot see a wrong
# update: they move ~1e-3 relative from step to step, mostly with the
# batch. Faults, read on the CPU at reduced size: no update reads a gap
# of 1; ZO coefficients taken from a loss over half the batch read gaps
# of 0.33 to 1.7 with losses within 1.4e-5.
#
# Phase c, chip against CPU, per matmul precision. The chip's default
# f32 matmul is one bf16 pass; its losses read 4.0e-5 off on a TPU v5
# lite, but the ZO coefficient divides a loss difference by mu = 1e-3,
# so that rounding reaches the update amplified: the gap is printed, not
# bounded. At HIGHEST the matmuls are f32-accurate (losses 9.1e-7 off)
# and the update is held below the half-batch fault's 0.33.
CHIP_VS_CPU = {"default": dict(loss=1e-3), "highest": dict(loss=1e-5,
                                                           gap=0.2)}
# --chips 4 against a 1-device mesh, at full width: the global batch
# mean becomes a mean of four shard means, another summation order. At
# step 0 (same state, same batch) the losses read 7.8e-8 apart on four
# TPU v5 lite chips; a step without the pmean returns one shard's loss
# (on the CPU at reduced size, 1 of 4 rows: 2.4e-2 off). After step 0
# the rounding, divided by mu, steers the updates apart: losses read up
# to 1.1e-4 apart and the update gap up to 0.44, at HIGHEST too (up to
# 0.45). So the gap only catches a step that updates nothing (1) or at a
# wrong scale; a step that updates each replica from its own shard is
# caught exactly, by the replicas' disagreeing (_check_split).
DATA_PARALLEL = dict(h0=1e-5, loss=2e-3, gap=0.75)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """JAX's own compile events: seconds spent in the backend compiler
    (a persistent-cache read included) and persistent-cache hits/writes."""

    def __init__(self):
        self.compile_s, self.hits, self.writes = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def _platforms(tree) -> set:
    return {d.platform for leaf in jax.tree.leaves(tree)
            for d in leaf.devices()}


def _flat(tree, m=None) -> np.ndarray:
    """The leaves of ``tree`` (of row ``m`` of each) as one host f64
    vector."""
    return np.concatenate([np.asarray(a if m is None else a[m],
                                      np.float64).ravel()
                           for a in jax.tree.leaves(tree)])


def param_moves(run) -> dict:
    """Final minus initial parameters of a vfl-zoo ``train.ZooRun``, for
    the server's w0 and each party's block. Call it under the device
    context the run had: ``run.init`` rebuilds the start state there."""
    start = run.init()
    q = jax.tree.leaves(start.parties)[0].shape[0]
    moves = {"w0": _flat(run.state.w0) - _flat(start.w0)}
    for m in range(q):
        moves[f"party{m}"] = (_flat(run.state.parties, m)
                              - _flat(start.parties, m))
    return moves


def update_gaps(got: dict, ref: dict) -> dict:
    """||got - ref|| / ||ref|| for each block of two ``param_moves``. A
    block the reference left untouched reads 0 when got left it
    untouched too, else inf."""
    gaps = {}
    for name, r in ref.items():
        err, n = np.linalg.norm(got[name] - r), np.linalg.norm(r)
        gaps[name] = float(err / n if n else (0.0 if err == 0 else np.inf))
    return gaps


def _gap_line(gaps: dict) -> str:
    return " ".join(f"{k}={v:.3e}" for k, v in gaps.items())


# ------------------------------------------------------------------ a ----

def phase_device(min_count: int = 1) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[a] platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__}", flush=True)
    check(dev["platform"] == "tpu", f"no TPU: JAX found {dev['platform']}")
    check(dev["count"] >= min_count,
          f"needs {min_count} chips, JAX found {dev['count']}")
    from repro.kernels.platform import interpret_mode
    check(not interpret_mode(), "kernels would interpret on this TPU")
    return dev


# ------------------------------------------------------------------ b ----

def phase_trainer(argv, label: str, meter: CompileMeter | None = None):
    """repro.launch.train.main on a vfl-zoo argv: every h finite, step 0
    near ln V, the state on this process's default platform."""
    c0 = meter.compile_s if meter else 0.0
    run = train.main(argv)
    h = np.asarray(run.losses)
    print(f"[b] {label}: h={np.array2string(h, precision=5)}", flush=True)
    check(np.isfinite(h).all(), f"{label}: non-finite h {h}")
    args = train.parse_args(argv)
    ln_v = math.log(get_config(args.arch, reduced=args.reduced).vocab_size)
    check(abs(h[0] - ln_v) < H0_TOL_NATS,
          f"{label}: step-0 h={h[0]:.4f} is not near ln V={ln_v:.4f}")
    where = _platforms(run.state)
    check(where == {jax.default_backend()},
          f"{label}: state lives on {where}")
    warm = float(np.median(run.step_s[1:]))
    compile_s = (meter.compile_s - c0) if meter else float("nan")
    print(f"[b] {label}: h0={h[0]:.5f} ln(V)={ln_v:.5f} state on {where}; "
          f"smoke timing, not a benchmark: compile {compile_s:.2f} s, "
          f"first step {run.step_s[0]:.3f} s (compile included), warm "
          f"step median {warm:.4f} s over {len(run.step_s) - 1} steps",
          flush=True)
    return h


# ------------------------------------------------------------------ c ----

def _hold(tag, label, got, ref, loss, gap=None, h0=None):
    """Check one (losses, param_moves) pair against its reference's: the
    relative loss difference at step 0 within ``h0`` and at every step
    within ``loss``, the largest update gap within ``gap``; a None bound
    is printed, not checked."""
    (h, moves), (h_ref, moves_ref) = got, ref
    rel = np.abs(h - h_ref) / np.abs(h_ref)
    gaps = update_gaps(moves, moves_ref)
    print(f"[{tag}] {label}: rel loss diff at step 0 {rel[0]:.3e} (tol "
          f"{h0}), max {rel.max():.3e} (tol {loss}); update gap (tol "
          f"{gap}): {_gap_line(gaps)}", flush=True)
    check(np.isfinite(h).all(), f"{label}: non-finite h {h}")
    check(h0 is None or rel[0] <= h0, f"{label}: step-0 rel loss diff "
          f"{rel[0]}")
    check(rel.max() <= loss, f"{label}: rel loss diff {rel.max()}")
    check(gap is None or max(gaps.values()) <= gap,
          f"{label}: update gap {gaps}")


def phase_chip_vs_cpu(argv):
    """The first steps of one config on the default device, at default
    and at HIGHEST matmul precision, against the host CPU, from the same
    seed."""
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = train.main(argv)
        ref = np.asarray(cpu.losses), param_moves(cpu)
    check(_platforms(cpu.state) == {"cpu"}, "CPU reference left the CPU")
    print(f"[c] cpu h={ref[0]}", flush=True)
    for precision, bounds in CHIP_VS_CPU.items():
        with jax.default_matmul_precision(precision):
            run = train.main(argv)
            got = np.asarray(run.losses), param_moves(run)
        print(f"[c] {jax.default_backend()} {precision} h={got[0]}",
              flush=True)
        _hold("c", f"{precision} precision vs cpu", got, ref, **bounds)


# ------------------------------------------------------------------ d ----

def phase_paper_lr(scale: float = 1.0, steps: int = 500):
    """core/asyrevel.train (examples/quickstart.py's trainer) on
    PaperLRModel at D4 a9a shapes (32,561 x 127 at scale 1, q = 8)."""
    from repro.core import asyrevel
    from repro.core.vfl import PaperLRModel, pad_features
    from repro.data.synthetic import make_paper_dataset

    q = 8
    (X, y), spec = make_paper_dataset("D4_a9a", scale=scale)
    model = PaperLRModel(PaperLRConfig(num_features=spec.d, num_parties=q))
    data = {"x": pad_features(jnp.asarray(X), spec.d, q),
            "y": jnp.asarray(y)}
    vfl = VFLConfig(num_parties=q, direction="gaussian", mu=1e-3,
                    lr_party=5e-2, lr_server=5e-2 / q, max_delay=4)
    state, losses = asyrevel.train(model, vfl, data, jax.random.key(0),
                                   steps=steps, batch_size=64)
    losses = np.asarray(losses)
    k = steps // 10
    first, last = float(losses[:k].mean()), float(losses[-k:].mean())
    acc = float(jnp.mean(model.predict(state.w0, state.parties, data["x"])
                         == data["y"]))
    print(f"[d] {spec.name} n={len(y)} d={spec.d} q={q} steps={steps}: "
          f"loss {first:.4f} (first {k}) -> {last:.4f} (last {k}), "
          f"train acc {acc:.3f}", flush=True)
    check(np.isfinite(losses).all(), "paper LR: non-finite loss")
    check(last < 0.9 * first, f"paper LR: loss did not fall ({first}->{last})")


# ------------------------------------------------------------------ e ----

def _close(name, got, want, tol):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    err = float(np.max(np.abs(got - want)))
    # the bound assert_allclose applies, element by element
    used = float(np.max(np.abs(got - want) / (tol + tol * np.abs(want))))
    print(f"[e] {name}: max abs err {err:.3e}, {used:.3f} of the bound "
          f"atol=rtol={tol}", flush=True)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol, err_msg=name)


def _bitwise(name, got, want):
    la, lb = jax.tree.leaves(got), jax.tree.leaves(want)
    check(len(la) == len(lb), f"{name}: tree mismatch")
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(la, lb))
    print(f"[e] {name}: bitwise={same}", flush=True)
    check(same, f"{name}: not bitwise equal to its oracle")


def _rand(shape, dtype, salt):
    return jax.random.normal(jax.random.fold_in(jax.random.key(42), salt),
                             shape, jnp.float32).astype(dtype)


def phase_kernels(interpret: bool = False, big: bool = True):
    """Every Pallas kernel at ``interpret`` against its kernels/ref.py
    oracle (tests/test_kernels.py's tolerances) or, where the tests pin
    bitwise parity, against the unfused eager oracle bit for bit. On the
    chip the references' XLA matmuls run at HIGHEST precision: the
    kernels accumulate f32 products, which the chip's default one-pass
    bf16 matmul would not."""
    from repro.core import zoo
    from repro.core.exchange import ZOExchange
    from repro.kernels import fused_round, ops, ref
    from repro.kernels.zo_update import zo_update_pallas

    hi = jax.default_matmul_precision("highest")
    for dtype, tol in ((jnp.float32, 2e-4), (jnp.bfloat16, 2e-2)):
        M, K, N = (1024, 1024, 1024) if big else (256, 512, 384)
        x, w = _rand((M, K), dtype, 1), _rand((K, N), dtype, 2)
        u = _rand((K, N), jnp.float32, 3)
        y0, y1 = ops.dual_matmul(x, w, u, mu=1e-2, interpret=interpret)
        with hi:
            r0, r1 = ref.dual_matmul_ref(x, w, u, mu=1e-2)
        name = f"dual_matmul {M}x{K}x{N} {jnp.dtype(dtype).name}"
        _close(name + " y0", y0, r0, tol)
        _close(name + " y1", y1, r1, tol)

    B, S, H, hd = (1, 2048, 16, 64) if big else (2, 256, 4, 64)
    q, k, v = (_rand((B, S, H, hd), jnp.float32, s) for s in (7, 8, 9))
    for causal in (True, False):
        o = ops.flash_attention(q, k, v, causal=causal, interpret=interpret)
        flat = [t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
                for t in (q, k, v)]
        with hi:
            o_ref = ref.flash_attention_ref(*flat, causal=causal)
        _close(f"flash_attention ({B * H},{S},{hd}) causal={causal}",
               o, o_ref.reshape(B, H, S, hd).transpose(0, 2, 1, 3), 2e-5)

    for n in ((151_936 * 256, 1000, 3) if big else (1000, 3)):
        w = _rand((n,), jnp.float32, 16)
        bits = jax.random.bits(jax.random.key(17), (n,), jnp.uint32)
        out = zo_update_pallas(w, bits, jnp.float32(0.03),
                               interpret=interpret)
        u = np.where((np.asarray(bits) & 1) == 1, np.float32(1),
                     np.float32(-1))
        _bitwise(f"zo_update N={n}", out,
                 np.asarray(w) - np.float32(0.03) * u)

    key = jax.random.key(211)
    wt = {"a": _rand((1100,), jnp.float32, 222),
          "b": _rand((7, 5), jnp.float32, 223)}
    coeff, lr = jnp.float32(0.37), 1e-2
    _bitwise("zo_apply pallas vs zoo.apply_zo_update",
             fused_round.zo_apply(wt, key, jnp.float32(lr * coeff),
                                  interpret=interpret),
             zoo.apply_zo_update(wt, key, "rademacher", coeff, lr))

    c = _rand((4, 512), jnp.float32, 210)
    for codec in ("f32", "bf16", "int8"):
        for mech in (None, "gaussian", "laplace"):
            dp = (None if mech is None else
                  DPConfig(noise_multiplier=1.1, clip=0.7, mechanism=mech))
            ex_u, ex_f = (ZOExchange.from_config(VFLConfig(
                num_parties=2, mu=1e-3, codec=codec, direction="rademacher",
                dp=dp, fused=f)) for f in (False, True))
            _bitwise(f"defended_encode {codec} dp={mech}",
                     fused_round.encode_up_fused(ex_f, c, key, impl="pallas",
                                                 interpret=interpret),
                     ex_u.encode_up(c, key))

    if not interpret:
        # no argument: the platform must pick the compiled kernel
        hlo = jax.jit(lambda a, b: zo_update_pallas(a, b, jnp.float32(1.0))
                      ).lower(jnp.zeros((1024,)),
                              jnp.zeros((1024,), jnp.uint32)
                              ).compile().as_text()
        check("tpu_custom_call" in hlo,
              "a kernel called without interpret= did not compile to Mosaic")
        print("[e] default interpret= resolves to the compiled kernel",
              flush=True)


# ---------------------------------------------------------- --chips 4 ----

def _check_split(run, args, cfg, n: int):
    """The batch of a --data-parallel n run lands on all n devices, and
    its replicated state on n devices, bit for bit the same on each: every
    device updates from the same all-reduced losses."""
    from repro.sharding.rules import batch_pspecs, shard_tree

    # a batch placed as the launcher places it, through the program the
    # run compiled (a cache hit): the shardings that program takes
    mesh = jax.tree.leaves(run.state)[0].sharding.mesh
    batch = train.make_batch_arrays(cfg, args.batch_size, args.seq_len, 0)
    batch = shard_tree(batch, mesh,
                       batch_pspecs(batch, mesh, batch_axes=("data",)))
    shardings = run.step.lower(run.state, batch).compile(
        ).input_shardings[0][1]
    for name, s in shardings.items():
        rows = s.shard_shape(batch[name].shape)[0]
        print(f"[4] batch '{name}' on {len(s.device_set)} devices, "
              f"{rows} of {args.batch_size} rows each", flush=True)
        check(len(s.device_set) == n and rows * n == args.batch_size
              and {d.platform for d in s.device_set}
              == {jax.default_backend()},
              f"batch '{name}' is not split over {n} devices: {s}")
    where = {d.id for leaf in jax.tree.leaves(run.state)
             for d in leaf.devices()}
    check(len(where) == n, f"sharded state on devices {where}")
    leaves = jax.tree.leaves(run.state)
    split = sum(not _replicas_agree(leaf) for leaf in leaves)
    print(f"[4] replicated state: {split} of {len(leaves)} leaves differ "
          f"between devices", flush=True)
    check(split == 0, f"the {n} replicas of the state disagree")


def _replicas_agree(leaf) -> bool:
    if jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key):
        leaf = jax.random.key_data(leaf)
    first, *rest = (np.asarray(s.data) for s in leaf.addressable_shards)
    return all(np.array_equal(first, r) for r in rest)


def phase_data_parallel(argv, n: int):
    """The launcher's sharded trainer (--data-parallel n) against the same
    steps on a 1-device mesh; the batch must shard over all n devices."""
    from repro.launch.mesh import make_data_mesh

    args = train.parse_args(argv)
    cfg = get_config(args.arch, reduced=args.reduced)
    run = train.main(argv + ["--data-parallel", str(n)])
    _check_split(run, args, cfg, n)
    got = np.asarray(run.losses), param_moves(run)
    warm = float(np.median(run.step_s[1:]))
    # device 0 cannot hold the replicated state beside the 1-device run
    del run
    single = train.run_vfl_zoo(args, cfg, mesh=make_data_mesh(1))
    ref = np.asarray(single.losses), param_moves(single)
    print(f"[4] {n}-device h={got[0]}\n[4] 1-device mesh h={ref[0]}\n"
          f"[4] smoke timing, not a benchmark: warm step median {warm:.4f} s"
          f" on {n} devices, {np.median(single.step_s[1:]):.4f} s on 1",
          flush=True)
    _hold("4", f"{n} vs 1 device", got, ref, **DATA_PARALLEL)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4: run only the sharded --data-parallel 4 "
                        "trainer against a 1-device mesh")
    opts = p.parse_args(argv)
    t0 = time.perf_counter()
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    dev = phase_device(min_count=opts.chips)
    print(f"[a] compile cache: {cache_dir}", flush=True)
    if opts.chips == 4:
        phase_data_parallel(TRAIN_ARGV, 4)
    else:
        phase_trainer(TRAIN_ARGV, "full width", meter)
        phase_trainer(TRAIN_ARGV + ["--steps", "4"] + DEFENDED_ARGV,
                      "defended", meter)
        phase_chip_vs_cpu(["--arch", "qwen1.5-0.5b", "--mode", "vfl-zoo",
                           "--reduced", "--parties", "4", "--steps", "3",
                           "--batch-size", "8", "--seq-len", "64"])
        phase_paper_lr()
        phase_kernels(interpret=False)
    print(f"compile cache: {cache_dir} hits={meter.hits} "
          f"writes={meter.writes} backend compile {meter.compile_s:.1f} s; "
          f"whole script {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
