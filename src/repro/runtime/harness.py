"""Federation harness: spawn the server + q party OS processes, supervise
them (respawning scripted crashers per the failure plan), and collect
results.

This is the piece a launcher or test talks to:

    result = run_federation({"kind": "lr", "parties": 2, ...}, rounds=6)
    result["server"]["history"]        # [(t, h), ...] — the loss curve
    result["server"]["bytes_by_kind"]  # measured per-kind wire accounting
    result["parties"][0]["final_w"]    # each party's final block

``run_reference`` runs the identical problem through the in-process
``HostAsyncTrainer.run_serial`` — the pair is how tests pin TCP-vs-memory
bit-identity and accounting parity.

Processes are started with the multiprocessing 'spawn' context (each
child gets a fresh jax runtime; fork would inherit locked XLA state) and
the repo's src dir is forced onto the children's PYTHONPATH so the
harness works from a bare pytest run as well as an installed package.

Every spawned role runs on ``CHILD_PLATFORM`` (the CPU). The server and
the parties stand for separate organisations' hosts, and a chip belongs
to one process at a time: q + 1 children racing the parent for one TPU
would fail or hang. Each role reports the platform it ran on.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import queue as queue_mod
import time

import numpy as np

from repro.configs.base import RuntimeConfig
from repro.core.async_host import HostAsyncTrainer
from repro.dp.accountant import resolve_spec_dp
# the harness is the monitor's parent-side entry point: it owns the env
# handoff to spawned children, so obs-discipline approves these two deep
# imports here (analysis/rules_obs.py) and nowhere else in runtime/
from repro.obs import MONITOR_ENV
from repro.obs.health import engine_from_spec
from repro.obs.monitor import MonitorServer
from repro.runtime.failures import NO_FAILURES, FailurePlan
from repro.runtime.party import party_main
from repro.runtime.problem import build_problem
from repro.runtime.server import FederationError, server_main

# JAX_PLATFORMS of every spawned server and party process
CHILD_PLATFORM = "cpu"


def export_env(env: dict) -> dict:
    """Put ``env`` where spawned children inherit it (a None value leaves
    that variable alone); returns the parent's values for ``restore_env``,
    which a run calls on exit so its settings never leak into later runs
    in this interpreter."""
    prev = {k: os.environ.get(k) for k, v in env.items() if v is not None}
    os.environ.update({k: env[k] for k in prev})
    return prev


def restore_env(prev: dict) -> None:
    for k, v in prev.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _ensure_child_pythonpath() -> None:
    # repro is a namespace package (its __file__ is None), so anchor on
    # this module: src/ is three levels up from harness.py
    src = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    paths = os.environ.get("PYTHONPATH", "").split(os.pathsep)
    if src not in paths:
        os.environ["PYTHONPATH"] = os.pathsep.join([src] + [p for p in paths
                                                            if p])


def _terminate(procs) -> None:
    for p in procs:
        if p is not None and p.is_alive():
            p.terminate()
    for p in procs:
        if p is not None:
            p.join(timeout=5.0)


def run_federation(spec: dict, rounds: int, *,
                   cfg: RuntimeConfig | None = None,
                   channel_kind: str = "inmemory",
                   plan: FailurePlan = NO_FAILURES,
                   ckpt_root: str | None = None,
                   resume: bool = False) -> dict:
    """Run one complete federation; returns {'server': ..., 'parties':
    {m: ...}, 'rejoins': int}. Raises FederationError on deadline or
    party failure the plan does not cover."""
    cfg = cfg or RuntimeConfig()
    # calibrate any DP target ONCE, in the parent: the resolved noise
    # multiplier rides the spec to the server and every party process,
    # so all endpoints derive the identical defended exchange
    spec = resolve_spec_dp(spec, rounds)
    q = int(spec.get("parties", 2))
    _ensure_child_pythonpath()
    if cfg.monitor and not cfg.trace_dir:
        raise ValueError("RuntimeConfig.monitor requires trace_dir "
                         "(the collector writes alerts/health there)")
    # what every child inherits: its platform, and trace capture (each
    # child lazily opens its own trace file on its first
    # obs.maybe_tracer() call, role = its mp process name)
    prev_env = export_env({"JAX_PLATFORMS": CHILD_PLATFORM,
                           "REPRO_TRACE_DIR": cfg.trace_dir or None})
    # live health plane: start the collector BEFORE spawning so every
    # child's tracer finds REPRO_MONITOR_ADDR at construction and mirrors
    # its records over the side socket (out-of-band: never a protocol
    # Message, pinned bitwise-invisible in tests)
    monitor = None
    if cfg.monitor:
        monitor = MonitorServer(cfg.trace_dir,
                                engine=engine_from_spec(spec, rounds))
        prev_env.update(export_env({MONITOR_ENV: monitor.addr}))
    ctx = mp.get_context("spawn")
    port_q = ctx.Queue()
    result_q = ctx.Queue()

    def party_ckpt(m: int) -> str | None:
        return (os.path.join(ckpt_root, f"party{m}")
                if ckpt_root is not None else None)

    server_ckpt = (os.path.join(ckpt_root, "server")
                   if ckpt_root is not None else None)

    server_proc = ctx.Process(
        target=server_main,
        args=(spec, rounds, cfg, channel_kind, server_ckpt, resume,
              port_q, result_q),
        name="fed-server", daemon=True)
    server_proc.start()
    procs: dict[int, mp.Process] = {}
    try:
        port = None
        port_wait = time.monotonic() + 60.0
        while port is None:
            try:
                port = port_q.get(timeout=0.5)
            except queue_mod.Empty:
                if server_proc.exitcode is not None:
                    # died during startup — surface its traceback, not
                    # an uninformative port timeout a minute later
                    try:
                        tag, payload = result_q.get(timeout=1.0)
                    except queue_mod.Empty:
                        tag, payload = "server_error", (
                            f"exitcode {server_proc.exitcode}, no report")
                    raise FederationError(f"server failed: {payload}")
                if time.monotonic() > port_wait:
                    raise FederationError(
                        "server never reported its port")

        def spawn_party(m: int, resume: bool):
            p = ctx.Process(
                target=party_main,
                args=(spec, m, port, rounds, cfg, plan.fault_for(m),
                      party_ckpt(m), resume, result_q),
                name=f"fed-party{m}", daemon=True)
            p.start()
            return p

        for m in range(q):
            procs[m] = spawn_party(m, resume=resume)

        rejoins_left = {m: (plan.fault_for(m).max_rejoins
                            if plan.fault_for(m) else 0) for m in range(q)}
        rejoins = 0
        results: dict = {"parties": {}}
        deadline = time.monotonic() + cfg.deadline_s
        while True:
            if time.monotonic() > deadline:
                raise FederationError(
                    f"harness deadline exceeded "
                    f"(got {len(results['parties'])}/{q} party results, "
                    f"server={'done' if 'server' in results else 'pending'})")
            # drain results
            try:
                tag, payload = result_q.get(timeout=0.25)
                if tag == "party":
                    results["parties"][payload["party"]] = payload
                elif tag == "server":
                    results["server"] = payload
                elif tag == "server_error":
                    raise FederationError(f"server failed: {payload}")
            except queue_mod.Empty:
                pass
            if (server_proc.exitcode is not None
                    and server_proc.exitcode != 0
                    and "server" not in results):
                # give a pending server_error report one more drain
                try:
                    tag, payload = result_q.get(timeout=1.0)
                    if tag == "server_error":
                        raise FederationError(f"server failed: {payload}")
                except queue_mod.Empty:
                    pass
                raise FederationError(
                    f"server exited with {server_proc.exitcode} before "
                    f"reporting a result")
            # supervise scripted crashes
            for m, p in list(procs.items()):
                if (p.exitcode is not None and p.exitcode != 0
                        and m not in results["parties"]):
                    if rejoins_left[m] <= 0:
                        raise FederationError(
                            f"party {m} exited with {p.exitcode} and no "
                            f"rejoin budget remains")
                    rejoins_left[m] -= 1
                    rejoins += 1
                    fault = plan.fault_for(m)
                    time.sleep(fault.rejoin_delay_s if fault else 0.5)
                    procs[m] = spawn_party(m, resume=True)
            if "server" in results and len(results["parties"]) == q:
                break
        results["rejoins"] = rejoins
        for p in list(procs.values()) + [server_proc]:
            p.join(timeout=10.0)
        if monitor is not None:
            results["monitor"] = monitor.stop()
        return results
    finally:
        restore_env(prev_env)
        _terminate(list(procs.values()) + [server_proc])
        if monitor is not None:
            monitor.stop()                 # idempotent: error paths too


def run_reference(spec: dict, rounds: int, channel=None):
    """The in-process deterministic reference for the same spec: returns
    (trainer, HostRunResult) from HostAsyncTrainer.run_serial. DP specs
    resolve through the same calibration as run_federation, so the
    memory-vs-TCP parity acceptance extends to defended runs."""
    prob = build_problem(resolve_spec_dp(spec, rounds))
    tr = HostAsyncTrainer(prob.model, prob.vfl, prob.X, prob.y,
                          batch_size=prob.batch_size, compute_cost_s=0.0,
                          seed=prob.seed, channel=channel)
    res = tr.run_serial(rounds)
    return tr, res


def history_losses(result: dict) -> np.ndarray:
    """The loss trajectory of a federation result, as an array."""
    return np.asarray([h for _, h in result["server"]["history"]],
                      np.float64)
