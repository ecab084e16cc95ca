"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 200 --mode lm
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 500 --mode vfl-zoo --parties 4
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 200 --mode vfl-zoo --parties 4 --data-parallel 4

Modes:
  lm       first-order Adam LM training (substrate baseline)
  vfl-zoo  the paper's AsyREVEL black-box VFL training of the same arch

--data-parallel N runs the vfl-zoo step through the sharded scale path
(launch/steps.py mesh=; docs/scale.md): batch sharded over a 1-D 'data'
mesh, server loss psum-reduced, params replicated.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Callable, NamedTuple

# --data-parallel N on CPU needs N XLA host devices, and that must be
# configured BEFORE jax initializes — so peek at argv before the jax
# import (both '--data-parallel N' and '--data-parallel=N' forms;
# malformed values fall through for argparse to report). No-op when jax
# is already in (library use / tests) or the operator set the flag.
def _peek_data_parallel(argv):
    for i, a in enumerate(argv):
        v = None
        if a == "--data-parallel" and i + 1 < len(argv):
            v = argv[i + 1]
        elif a.startswith("--data-parallel="):
            v = a.split("=", 1)[1]
        if v is not None:
            try:
                return int(v)
            except ValueError:
                return None
    return None


_dp = _peek_data_parallel(sys.argv)
if _dp is not None and "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if _dp > 1 and "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            f"{_flags} --xla_force_host_platform_device_count={_dp}".strip())

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import INPUT_SHAPES, VFLConfig, get_config
from repro.core.asyrevel import AsyState
from repro.data.synthetic import make_lm_dataset
from repro.launch import steps as step_lib
from repro.models import build_model
from repro.obs.metrics import ObsMetricLogger
from repro.optim.schedules import make_schedule
from repro.utils.compile_cache import enable_compile_cache


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--mode", default="lm", choices=["lm", "vfl-zoo"])
    p.add_argument("--reduced", action="store_true",
                   help="2-layer smoke-size variant (CPU-friendly)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--schedule", default=None,
                   help="constant|cosine|wsd (default: arch-appropriate)")
    p.add_argument("--parties", type=int, default=4)
    p.add_argument("--data-parallel", type=int, default=1,
                   help="shard the vfl-zoo batch over N devices "
                        "(sharded scale path; forces N host devices on "
                        "CPU when launched as __main__)")
    p.add_argument("--network", default=None,
                   choices=["lan", "wan", "straggler"],
                   help="price the vfl-zoo run's wire traffic on a "
                        "NetworkChannel profile (configs.NETWORK_PROFILES)"
                        " and report the simulated transport time")
    p.add_argument("--transport", default="memory",
                   choices=["memory", "tcp"],
                   help="memory: in-process executors over the simulated "
                        "wire; tcp: the multi-process federation runtime "
                        "(repro/runtime) — server + one OS process per "
                        "party over real sockets (docs/runtime.md)")
    p.add_argument("--dropout-at", type=int, default=None,
                   help="tcp only: scripted fault — crash party 0 at "
                        "this round and rejoin it from checkpoint")
    p.add_argument("--mu", type=float, default=1e-3)
    p.add_argument("--fused", action="store_true",
                   help="vfl-zoo only: route every release through the "
                        "fused kernels/fused_round fast path (perturb + "
                        "clip + DP noise + codec as single dispatches; "
                        "bit-identical to the unfused seam — "
                        "docs/kernels.md)")
    p.add_argument("--codec", default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="vfl-zoo only: up-link payload codec for the c "
                        "values at the exchange seam (core/exchange.py; "
                        "int8 = symmetric per-message quantization)")
    p.add_argument("--opt-state-dtype", default="f32",
                   choices=["f32", "bf16"],
                   help="lm only: storage dtype of the Adam moments "
                        "(bf16 halves optimizer memory; arithmetic stays "
                        "f32 — optim/optimizers.py)")
    p.add_argument("--dp-epsilon", type=float, default=None,
                   help="vfl-zoo only: defend the party->server upload "
                        "seam with clip-then-noise DP calibrated to this "
                        "per-party (eps, delta) target over the run "
                        "(repro/dp, docs/dp.md); 'inf' turns the "
                        "subsystem transparently off")
    p.add_argument("--dp-delta", type=float, default=None,
                   help="DP delta (default 1e-5); requires --dp-epsilon")
    p.add_argument("--dp-clip", type=float, default=None,
                   help="per-entry clip bound C on the uploaded c values "
                        "— the mechanism's sensitivity; REQUIRED with a "
                        "finite --dp-epsilon")
    p.add_argument("--serve", type=int, default=None,
                   help="vfl-zoo only: serve this many inference requests "
                        "through the federated serving engine instead of "
                        "training — every occupied slot rides ONE wire "
                        "crossing per party per step (serving/federated.py, "
                        "docs/serving.md); composes with --network (priced "
                        "simulated wire) or --transport tcp (real party "
                        "processes; --ckpt-dir serves checkpointed blocks)")
    p.add_argument("--serve-batch", type=int, default=None,
                   help="concurrent serving slots = max wire batch B "
                        "(default ServingConfig.slots); requires --serve")
    p.add_argument("--serve-cache", type=int, default=None,
                   help="per-party LRU answer-cache capacity, keyed "
                        "(sample id, params version) (default "
                        "ServingConfig.cache_entries); requires --serve")
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="capture per-process JSONL traces under DIR "
                        "(repro/obs; docs/observability.md) — spans, wire "
                        "crossings, heartbeat RTT, epsilon spend. Tracing "
                        "is bitwise-invisible: the run's math, RNG "
                        "streams, and wire bytes are untouched. Merge "
                        "with `python -m repro.obs DIR`")
    p.add_argument("--monitor", action="store_true",
                   help="live health plane on top of --trace: a collector "
                        "in the parent receives every record over a side "
                        "socket as it is emitted, online detectors "
                        "(straggler, divergence, DP burn, byte drift, "
                        "RTT, chain decay) append to alerts.jsonl, and "
                        "a crashed process's last records are recovered "
                        "from the collector's flight ring. Watch live "
                        "with `python -m repro.obs.live DIR` "
                        "(docs/observability.md); still bitwise-invisible")
    p.add_argument("--straggler-s", type=float, default=None,
                   metavar="SEC",
                   help="tcp only: scripted fault — delay the LAST "
                        "party's uploads by SEC seconds every round (the "
                        "straggler the health plane's EWMA detector "
                        "flags; composes with --dropout-at, which "
                        "crashes party 0)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--resume", action="store_true",
                   help="restore from --ckpt-dir at latest_step before "
                        "training (all modes; with --transport tcp every "
                        "process restores its own state)")
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args(argv)

    # incoherent combinations die HERE with a clear argparse error, not
    # deep inside jax/socket setup
    if args.transport == "tcp":
        if args.mode != "vfl-zoo":
            p.error("--transport tcp runs the federated protocol; "
                    "it requires --mode vfl-zoo")
        if args.data_parallel > 1:
            p.error("--transport tcp runs parties as separate OS "
                    "processes; --data-parallel shards the in-process "
                    "scan trainer — the two paths are mutually exclusive")
        if args.network:
            p.error("--network prices a SIMULATED channel; the tcp "
                    "transport measures real socket traffic — drop one "
                    "of the two flags")
    if args.dropout_at is not None and args.transport != "tcp":
        p.error("--dropout-at injects a process crash; it requires "
                "--transport tcp")
    if args.straggler_s is not None:
        if args.transport != "tcp":
            p.error("--straggler-s stalls a real party process's uploads; "
                    "it requires --transport tcp")
        if args.straggler_s <= 0:
            p.error("--straggler-s must be a positive delay in seconds")
        if args.parties < 2:
            p.error("--straggler-s stalls the LAST party so the others "
                    "define the reference pace; it requires --parties >= 2")
    if args.monitor:
        if not args.trace:
            p.error("--monitor scores the live trace stream; it requires "
                    "--trace DIR (alerts.jsonl / health.json land there)")
        if args.mode != "vfl-zoo":
            p.error("--monitor watches the federated health plane; it "
                    "requires --mode vfl-zoo")
    if args.serve is not None:
        if args.mode != "vfl-zoo":
            p.error("--serve drives the federated serving round; it "
                    "requires --mode vfl-zoo")
        if args.serve <= 0:
            p.error("--serve must be a positive request count")
        if args.dropout_at is not None:
            p.error("--dropout-at scripts a TRAINING fault; the serving "
                    "path has no round schedule to crash at")
        if args.straggler_s is not None:
            p.error("--straggler-s scripts a TRAINING fault; the serving "
                    "path has no round schedule to stall")
        if args.resume:
            p.error("--resume restores training state; serving reads "
                    "checkpoints directly via --ckpt-dir")
        if args.dp_epsilon is not None:
            p.error("--dp-epsilon defends training releases keyed by "
                    "round; the serving answer is a deterministic keyless "
                    "release — serve undefended (docs/serving.md)")
    elif args.serve_batch is not None or args.serve_cache is not None:
        p.error("--serve-batch/--serve-cache size the serving engine; "
                "they require --serve")
    if args.resume and not args.ckpt_dir:
        p.error("--resume restores from --ckpt-dir; pass --ckpt-dir")
    # DP defends the vfl-zoo upload seam; incoherent combos die here
    import math as _math
    if args.dp_epsilon is not None:
        if args.mode != "vfl-zoo":
            p.error("--dp-epsilon defends the party->server upload seam "
                    "of the vfl-zoo protocol; --mode lm has no federated "
                    "boundary (and gradient-emitting frameworks like tig "
                    "leak on the DOWN-link, which upload noise cannot "
                    "defend — see docs/dp.md)")
        if args.dp_epsilon <= 0:
            p.error("--dp-epsilon must be > 0 (use 'inf' to disable)")
        if _math.isfinite(args.dp_epsilon) and args.dp_clip is None:
            p.error("--dp-epsilon without --dp-clip is incoherent: the "
                    "mechanism's sensitivity IS the clip bound")
    else:
        if args.dp_clip is not None or args.dp_delta is not None:
            p.error("--dp-clip/--dp-delta configure the DP mechanism; "
                    "they require --dp-epsilon")
    if args.fused and args.mode != "vfl-zoo":
        p.error("--fused fuses the vfl-zoo release hot path "
                "(kernels/fused_round); --mode lm has no exchange seam")
    if args.codec != "f32" and args.mode != "vfl-zoo":
        p.error("--codec compresses the vfl-zoo up-link payloads; "
                "--mode lm has no exchange seam")
    if args.opt_state_dtype != "f32" and args.mode != "lm":
        p.error("--opt-state-dtype quantizes the Adam moments of the "
                "first-order lm trainer; vfl-zoo keeps no Adam state")
    if args.dp_delta is None:
        args.dp_delta = 1e-5
    return args


def make_dp(args):
    """The run's DPConfig from the --dp-* flags (None when undefended).
    Calibration to a noise multiplier happens where the round budget is
    known: resolve_dp here for the in-process path, resolve_spec_dp in
    the federation harness for --transport tcp. ``--steps`` is the
    per-party round budget on tcp and a conservative upper bound for the
    scan trainer (one activated party per step)."""
    if args.dp_epsilon is None:
        return None
    from repro.configs import DPConfig
    from repro.dp.accountant import resolve_dp
    return resolve_dp(DPConfig(epsilon=args.dp_epsilon,
                               delta=args.dp_delta, clip=args.dp_clip),
                      rounds=args.steps)


def make_batch_arrays(cfg, n, seq_len, seed):
    toks, targets = make_lm_dataset(n, seq_len, cfg.vocab_size, seed)
    data = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(targets)}
    if cfg.enc_dec:
        rng = np.random.default_rng(seed + 1)
        data["frames"] = jnp.asarray(rng.normal(
            size=(n, cfg.encoder_frames, cfg.d_model)).astype(np.float32))
    if cfg.frontend == "vq_stub":
        rng = np.random.default_rng(seed + 2)
        data["modality_mask"] = jnp.asarray(
            (rng.random((n, seq_len)) < 0.3).astype(np.int32))
    return data


def role_platforms(srv: dict, parties: dict) -> str:
    """'server:cpu,party0:cpu,...' — the JAX platform each role ran on."""
    roles = [f"server:{srv['platform']}"] + [
        f"party{m}:{parties[m]['platform']}" for m in sorted(parties)]
    return ",".join(roles)


def run_tcp(args, cfg, log):
    """--transport tcp: the multi-process federation runtime. The server
    and each party are separate OS processes over real sockets running
    the paper's scalar-c host protocol; the arch sets the vertical
    feature width (d_model). Checkpoint/resume and scripted dropout are
    wired through repro/runtime (docs/runtime.md)."""
    from repro.configs import RuntimeConfig
    from repro.runtime import (FailurePlan, PartyFault, history_losses,
                               run_federation)

    spec = {"kind": "lr", "parties": args.parties,
            "features": cfg.d_model, "samples": max(64, args.batch_size * 8),
            "batch": args.batch_size, "seed": args.seed,
            "vfl": {"mu": args.mu, "lr_party": args.lr,
                    "lr_server": args.lr / args.parties}}
    if args.fused:
        spec["vfl"]["fused"] = True
    if args.codec != "f32":
        spec["vfl"]["codec"] = args.codec
    if args.dp_epsilon is not None:
        # the TARGET rides the spec; run_federation calibrates the noise
        # multiplier once and ships the resolved value to every process
        spec["vfl"]["dp"] = {"epsilon": args.dp_epsilon,
                             "delta": args.dp_delta, "clip": args.dp_clip}
    faults = {}
    if args.dropout_at is not None:
        faults[0] = PartyFault(crash_at_round=args.dropout_at)
    if args.straggler_s is not None:
        # the LAST party straggles — never party 0, so the stall composes
        # with --dropout-at's party-0 crash in one run
        faults[args.parties - 1] = PartyFault(slow_send_s=args.straggler_s)
    plan = FailurePlan(faults)
    # the federation deadline scales with the requested work — the
    # default 300 s hard wall would kill any long run; 2 s per round
    # comfortably covers socket round-trips + per-process jit compiles
    # (plus the scripted stall, every round, on the straggling party)
    per_round = 2.0 + (args.straggler_s or 0.0)
    cfg_rt = RuntimeConfig(
        deadline_s=max(300.0, 120.0 + per_round * args.steps * args.parties),
        trace_dir=args.trace, monitor=args.monitor)
    res = run_federation(spec, rounds=args.steps, plan=plan, cfg=cfg_rt,
                         ckpt_root=args.ckpt_dir, resume=args.resume)
    log.log(0, platforms=role_platforms(srv=res["server"],
                                         parties=res["parties"]))
    h = history_losses(res)
    srv = res["server"]
    # a --resume of an already-complete federation has no new rounds
    final_h = float(h[-1]) if len(h) else float("nan")
    extra = ({"dp_epsilon": args.dp_epsilon}
             if args.dp_epsilon is not None else {})
    if "monitor" in res:
        extra["alerts"] = len(res["monitor"]["alerts"])
    log.log(args.steps, transport="tcp", updates=srv["updates"],
            h=final_h, rejoins=res["rejoins"], **extra,
            disconnects=srv["disconnects"],
            wire_up_bytes=sum(srv["bytes_by_kind"].get(k, 0)
                              for k in ("c_up", "c_hat_up")),
            wire_down_bytes=srv["bytes_by_kind"].get("loss_down", 0),
            socket_bytes=srv["socket_bytes_in"] + srv["socket_bytes_out"])
    return final_h


def run_serve(args, cfg, log):
    """--serve N: federated inference serving (docs/serving.md). Builds
    the same runtime problem spec as --transport tcp training, then
    serves N requests through serving/federated.py — in-process party
    backends on the memory transport (optionally priced by --network),
    real party processes answering over sockets on tcp (with blocks
    restored from --ckpt-dir when given)."""
    from repro.configs import NETWORK_PROFILES, ServingConfig

    sc = ServingConfig(
        requests=args.serve,
        slots=args.serve_batch if args.serve_batch is not None
        else ServingConfig.slots,
        cache_entries=args.serve_cache if args.serve_cache is not None
        else ServingConfig.cache_entries)
    spec = {"kind": "lr", "parties": args.parties,
            "features": cfg.d_model, "samples": max(64, args.batch_size * 8),
            "batch": args.batch_size, "seed": args.seed,
            "vfl": {"mu": args.mu, "lr_party": args.lr,
                    "lr_server": args.lr / args.parties}}
    if args.codec != "f32":
        spec["vfl"]["codec"] = args.codec
    rng = np.random.default_rng(args.seed)
    sample_ids = rng.integers(0, spec["samples"], sc.requests)

    if args.transport == "tcp":
        from repro.configs import RuntimeConfig
        from repro.runtime.serving import run_tcp_serving
        cfg_rt = RuntimeConfig(
            deadline_s=max(300.0, 120.0 + 0.1 * sc.requests),
            trace_dir=args.trace, monitor=args.monitor)
        res = run_tcp_serving(spec, sample_ids, cfg=cfg_rt, slots=sc.slots,
                              cache_entries=sc.cache_entries,
                              ckpt_root=args.ckpt_dir)
        met = res["metrics"]
        log.log(0, platforms=role_platforms(srv=res, parties=res["parties"]))
        extra = ({"alerts": len(res["monitor"]["alerts"])}
                 if "monitor" in res else {})
        log.log(sc.requests, transport="tcp", served=met["served"],
                steps=met["steps"], cache_hits=met["cache_hits"],
                bytes_per_prediction=met["bytes_per_prediction"], **extra)
        return float(met["served"])

    from repro.core.wire import NetworkChannel
    from repro.runtime.problem import build_problem
    from repro.serving.federated import FederatedServingEngine, ServeRequest

    prob = build_problem(spec)
    channel = (NetworkChannel(NETWORK_PROFILES[args.network],
                              seed=args.seed) if args.network else None)
    eng = FederatedServingEngine.from_problem(
        prob, channel=channel, slots=sc.slots,
        cache_entries=sc.cache_entries)
    for i, sid in enumerate(sample_ids):
        eng.submit(ServeRequest(rid=i, sample_id=int(sid)))
    eng.run()
    eng.validate_wire()      # measured bytes == analytic, every run
    met = eng.metrics()
    extra = ({"network": args.network, "wire_s": met["wire_s"],
              "requests_per_s": met["requests_per_s"],
              "p50_s": met["p50_s"], "p99_s": met["p99_s"]}
             if args.network else {})
    log.log(sc.requests, transport="memory", served=met["served"],
            steps=met["steps"], cache_hits=met["cache_hits"],
            bytes_per_prediction=met["bytes_per_prediction"], **extra)
    return float(met["served"])


def main(argv=None):
    """Run the launcher. Returns the final loss (lm), the ``ZooRun`` of an
    in-process vfl-zoo run, the last server loss of a tcp federation, or
    the number of predictions served (--serve)."""
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    monitor = None
    if args.trace:
        from repro import obs
        if args.monitor and args.transport != "tcp":
            # in-process modes have no harness to own the collector: the
            # launcher is collector AND sole producer, so the monitor must
            # exist (and its address be exported) BEFORE obs.configure
            # dials the stream. On tcp the harness/serving parent owns it.
            from repro.obs.health import HealthEngine
            from repro.obs.monitor import MonitorServer
            monitor = MonitorServer(args.trace, engine=HealthEngine())
            os.environ[obs.MONITOR_ENV] = monitor.addr
        # the launcher process's own tracer (metric records + any
        # in-process executor spans); spawned tcp children configure
        # themselves from RuntimeConfig.trace_dir via the harness env var
        obs.configure(args.trace, role="launch")
    try:
        return _dispatch(args, cfg)
    finally:
        if monitor is not None:
            from repro import obs
            os.environ.pop(obs.MONITOR_ENV, None)
            obs.configure(None)     # goodbye frame, then stop the collector
            monitor.stop()


def _dispatch(args, cfg):
    if args.serve is not None:
        return run_serve(args, cfg,
                         ObsMetricLogger(f"serve:{args.arch}:vfl-zoo"))
    if args.transport == "tcp":
        return run_tcp(args, cfg,
                       ObsMetricLogger(f"train:{args.arch}:vfl-zoo-tcp"))
    if args.mode == "vfl-zoo":
        return run_vfl_zoo(args, cfg)
    model, key, n, data = _model_and_data(args, cfg)
    log = ObsMetricLogger(f"train:{args.arch}:lm")
    sched_name = args.schedule or (
        "wsd" if args.arch.startswith("minicpm") else "cosine")
    sched = make_schedule(sched_name, args.lr, args.steps,
                          warmup=max(1, args.steps // 20))
    state = step_lib.make_train_state(
        model, key,
        state_dtype=(jnp.bfloat16 if args.opt_state_dtype == "bf16"
                     else jnp.float32))
    start_step = 0
    rng = np.random.default_rng(args.seed)
    if args.resume:
        from repro.checkpoint import latest_step, restore_checkpoint
        step0 = latest_step(args.ckpt_dir)
        if step0 is not None:
            restored, _ = restore_checkpoint(
                args.ckpt_dir,
                {"params": state.params, "opt": state.opt}, step0)
            # a CONTINUATION, not a warm-started replay: optimizer
            # moments and the schedule step resume where they were,
            # and the data stream fast-forwards past consumed batches
            state = step_lib.TrainState(
                restored["params"], restored["opt"],
                jnp.asarray(step0, jnp.int32))
            start_step = step0
            for _ in range(step0):
                rng.integers(0, n, args.batch_size)
            log.log(0, resumed_from=step0)
    train_step = jax.jit(step_lib.make_train_step(model, sched))
    t0 = time.perf_counter()
    for s in range(args.steps):
        idx = rng.integers(0, n, args.batch_size)
        batch = jax.tree.map(lambda a: a[idx], data)
        state, (loss, metrics) = train_step(state, batch)
        if s % args.log_every == 0 or s == args.steps - 1:
            log.log(start_step + s, loss=loss, ce=metrics["ce"],
                    aux=metrics["aux"], lr=sched(start_step + s))
    dt = time.perf_counter() - t0
    log.log(args.steps, done=1, steps_per_s=args.steps / dt)
    if args.ckpt_dir:
        # a resumed run commits PAST the restored step, or the next
        # resume would restore the pre-continuation checkpoint and
        # silently discard this run's work
        save_checkpoint(args.ckpt_dir, start_step + args.steps,
                        {"params": state.params, "opt": state.opt},
                        {"arch": args.arch, "mode": "lm"})
    return float(loss)


class ZooRun(NamedTuple):
    """What an in-process vfl-zoo run leaves: the final AsyREVEL state,
    the per-step server losses h, per-step wall seconds, each taken
    around ``block_until_ready`` (step 0 includes the compile), the
    jitted step it called, and ``init``, which rebuilds the state a run
    without ``--resume`` starts from."""
    state: AsyState
    losses: list
    step_s: list
    step: Callable
    init: Callable[[], AsyState]


def _model_and_data(args, cfg):
    model = build_model(cfg)
    key = jax.random.key(args.seed)
    n = max(64, args.batch_size * 8)
    return model, key, n, make_batch_arrays(cfg, n, args.seq_len, args.seed)


def run_vfl_zoo(args, cfg, mesh=None) -> ZooRun:
    """--mode vfl-zoo in process: the paper's technique wrapping this
    architecture (launch/steps.make_vfl_zoo_step), one jitted step per
    minibatch. ``mesh`` runs the sharded path on that mesh; by default
    ``--data-parallel N > 1`` builds one over the first N devices."""
    model, key, n, data = _model_and_data(args, cfg)
    log = ObsMetricLogger(f"train:{args.arch}:vfl-zoo")
    assert cfg.d_model % args.parties == 0, \
        f"--parties must divide d_model={cfg.d_model}"
    dp = make_dp(args)
    vfl = VFLConfig(num_parties=args.parties, mu=args.mu,
                    lr_party=args.lr, lr_server=args.lr / args.parties,
                    dp=dp, fused=args.fused, codec=args.codec)
    if dp is not None:
        log.log(0, dp_epsilon=args.dp_epsilon,
                dp_sigma=(dp.noise_multiplier
                          if dp.noise_multiplier is not None else 0.0))
    if mesh is None and args.data_parallel > 1:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(args.data_parallel)
    if mesh is not None:
        assert args.batch_size % mesh.size == 0, \
            "--batch-size must divide by --data-parallel"
        log.log(0, data_parallel=mesh.size, devices=len(jax.devices()))
    vm, init, step = step_lib.make_vfl_zoo_step(model, vfl, mesh=mesh)
    state = init(key)
    start_step = 0
    rng = np.random.default_rng(args.seed)
    if args.resume:
        from repro.checkpoint import latest_step, restore_checkpoint
        step0 = latest_step(args.ckpt_dir)
        if step0 is not None:
            start_step = step0
            restored, _ = restore_checkpoint(
                args.ckpt_dir,
                {"w0": state.w0, "parties": state.parties,
                 "hist": state.hist}, step0)
            # the FULL AsyState: hist (the tau-delay ring buffer) is
            # checkpointed too — rebuilding it from the restored blocks
            # would hand the first tau resumed steps fresher stale
            # params than the uninterrupted run saw. step continues at
            # step0 (asyrevel_step folds the perturbation key by
            # state.step — restarting at 0 would REPLAY the original
            # direction sequence, not continue it) and the batch stream
            # fast-forwards past consumed draws.
            state = state._replace(w0=restored["w0"],
                                   parties=restored["parties"],
                                   hist=restored["hist"],
                                   step=jnp.asarray(step0, jnp.int32))
            for _ in range(step0):
                rng.integers(0, n, args.batch_size)
            log.log(0, resumed_from=step0)
    place = None
    if mesh is not None:
        # state replicated and every batch split over 'data', where the
        # sharded step keeps them: the first call then compiles the
        # steady-state program, and no later call recompiles for a
        # change of placement
        from repro.sharding.rules import (batch_pspecs, replicated_pspecs,
                                          shard_tree)
        state = shard_tree(state, mesh, replicated_pspecs(state))
        place = lambda b: shard_tree(  # noqa: E731
            b, mesh, batch_pspecs(b, mesh, batch_axes=("data",)))
    zoo_step = jax.jit(step)
    losses, step_s = [], []
    for s in range(args.steps):
        idx = rng.integers(0, n, args.batch_size)
        batch = jax.tree.map(lambda a: a[idx], data)
        if place is not None:
            batch = place(batch)
        t0 = time.perf_counter()
        state, h = jax.block_until_ready(zoo_step(state, batch))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(h))
        if s % args.log_every == 0 or s == args.steps - 1:
            log.log(start_step + s, h=h)
    log.log(args.steps, done=1, first_step_s=step_s[0],
            warm_step_s=float(np.median(step_s[1:])) if len(step_s) > 1
            else float("nan"))
    if args.network:
        # the scan trainer exchanges the same per-round payloads as the
        # host executor; price them on the chosen channel profile so the
        # run reports its simulated transport time next to wall-clock
        from repro.configs import NETWORK_PROFILES
        from repro.core.exchange import ZOExchange
        from repro.core.wire import SERVER, Message, NetworkChannel
        from repro.core.wire import party as wire_party

        ex = ZOExchange.from_config(vfl)
        ch = NetworkChannel(NETWORK_PROFILES[args.network], seed=args.seed)
        c0 = np.zeros((args.batch_size, args.seq_len,
                       cfg.d_model // args.parties), np.float32)
        nb = ex.codec.nbytes(c0)
        K = vfl.num_directions
        for s in range(args.steps):
            p0 = wire_party(s % args.parties)
            msgs = ([Message.make("c_up", p0, SERVER, s, None, nbytes=nb)]
                    + [Message.make("c_hat_up", p0, SERVER, s, None,
                                    nbytes=nb) for _ in range(K)]
                    + [Message.make("loss_down", SERVER, p0, s,
                                    tuple([0.0] * (1 + K)))])
            ch.measure_round_s(msgs)
        log.log(args.steps, network=args.network, wire_s=ch.time_s,
                wire_up_mb=ch.up_bytes / 1e6,
                wire_down_bytes=ch.down_bytes)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, start_step + args.steps,
                        {"w0": state.w0, "parties": state.parties,
                         "hist": state.hist},
                        {"arch": args.arch, "mode": "vfl-zoo"})
    return ZooRun(state, losses, step_s, zoo_step, lambda: init(key))


if __name__ == "__main__":
    main()
