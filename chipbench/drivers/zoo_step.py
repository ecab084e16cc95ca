"""Driver ``zoo_step``: the program's vfl-zoo step
(``launch/steps.make_vfl_zoo_step``) driven one round at a time, as
``launch/train.run_vfl_zoo`` drives it: the batch index is drawn on the
host, the rows are gathered from a token table on the device, the jitted
step is called, and ``h`` is read back before the next round.

With ``"mesh"`` > 1 in the traffic file the step is the sharded one on a
1-D data mesh over that many chips (the launcher's ``--data-parallel``):
the state is replicated and each batch split over the mesh.

Set-up builds one step and one state, drives them through the first
``check_rounds`` rounds on rows that all differ (the compile happens in
the first), and hands the same step and state to the window. The
reference follows those rounds once the window has closed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check as chk
from chipbench.common import load_module, seed_key
from chipbench.traffic_gen import lm_table
from chipbench.weights import fill


def vfl_config(cfg: dict):
    from repro.configs import VFLConfig
    v = cfg["vfl"]
    return VFLConfig(num_parties=v["num_parties"],
                     party_hidden=v["party_hidden"],
                     max_delay=v["max_delay"], mu=v["mu"],
                     lr_party=v["lr_party"], lr_server=v["lr_server"],
                     direction=v["direction"], codec=v["codec"],
                     lam=v["lam"], fused=v["fused"])


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices = devices
        self.family = load_module("families", cfg["family"])
        self.mcfg = self.family.program_config(cfg)
        self.vfl = vfl_config(cfg)
        self.B, self.S = traffic["batch"], traffic["seq"]
        self.rows = traffic["table_rows"]
        self.n_check = traffic["check_rounds"]
        self.std = cfg["model"]["initializer_range"]
        self.chips = len(devices)

    # ---------------------------------------------------------- set-up --
    def _start_trees(self):
        """The start state's w0 and stacked party tree, from the seed."""
        key = seed_key(self.seed)
        return (fill(self.shapes.w0, jax.random.fold_in(key, 1), self.std),
                fill(self.shapes.parties, jax.random.fold_in(key, 2),
                     self.std),
                self._state_key())

    def _state_key(self):
        return jax.random.fold_in(seed_key(self.seed), 3)

    def setup(self):
        from repro.core.asyrevel import AsyState
        from repro.launch import steps as step_lib
        from repro.models import build_model

        t0 = time.perf_counter()
        mesh = None
        if self.traffic["mesh"] > 1:
            from repro.launch.mesh import make_data_mesh
            mesh = make_data_mesh(self.traffic["mesh"])
        _, init, step = step_lib.make_vfl_zoo_step(
            build_model(self.mcfg), self.vfl, mesh=mesh)
        self.shapes = jax.eval_shape(init, jax.random.key(0))
        w0, parties, state_key = self._start_trees()
        tau = self.vfl.max_delay
        hist = jax.jit(lambda p: jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (tau + 1,) + a.shape),
            p))(parties)
        # held by self.state alone: a second reference would keep the
        # start state on the device through the first rounds and set the
        # run's memory peak
        self.state = AsyState(w0, parties, hist, jnp.zeros((), jnp.int32),
                              state_key)
        del w0, parties, hist
        toks, targets = lm_table(self.rows, self.S, self.mcfg.vocab_size,
                                 self.seed)
        self.table_host = {"tokens": toks, "targets": targets}
        self.data = {k: jnp.asarray(v) for k, v in self.table_host.items()}
        self.place = lambda b: b  # noqa: E731
        if mesh is not None:
            from repro.sharding.rules import (batch_pspecs,
                                              replicated_pspecs, shard_tree)
            self.state = shard_tree(self.state, mesh,
                                    replicated_pspecs(self.state))
            self.place = lambda b: shard_tree(  # noqa: E731
                b, mesh, batch_pspecs(b, mesh, batch_axes=("data",)))
        self.step = jax.jit(step)
        self.rng = np.random.default_rng(self.seed)
        # the first rounds, on rows that all differ, through the
        # window's own call and feed; the first compiles
        first = self.rng.permutation(self.rows)[: self.n_check * self.B]
        self.check_idx = first.reshape(self.n_check, self.B)
        self.h_prog, self.snaps, self.snap_s = [], [], 0.0
        self.phases = {"state and table": time.perf_counter() - t0}
        for r, idx in enumerate(self.check_idx):
            t = time.perf_counter()
            self.state, h = self._round(idx)
            self.h_prog.append(float(h))
            self.phases[f"round {r}"] = time.perf_counter() - t
            if r in (0, self.n_check - 1):
                t = time.perf_counter()
                self.snaps.append(self._snapshot())
                self.snap_s += time.perf_counter() - t

    def _round(self, idx):
        batch = self.place(jax.tree.map(lambda a: a[idx], self.data))
        return self.step(self.state, batch)

    def _snapshot(self) -> dict:
        q = self.vfl.num_parties
        named = chk.named_leaves(self.state.w0, self.state.parties, q)
        host = {}
        for name, a in named.items():
            if isinstance(a, tuple):
                j, arr = a
                host[name] = np.asarray(arr[j])
            else:
                host[name] = np.asarray(a)
        return host

    # ---------------------------------------------------------- window --
    def window(self, seconds: float, annotate: bool = False) -> dict:
        span = (jax.profiler.TraceAnnotation if annotate
                else _NoSpan)
        round_s, phases, rounds, failed = [], [], 0, 0
        t0 = time.perf_counter()
        with span("chipbench.window"):
            while time.perf_counter() - t0 < seconds:
                t = time.perf_counter()
                with span("chipbench.draw"):
                    idx = self.rng.integers(0, self.rows, self.B)
                    batch = self.place(jax.tree.map(lambda a: a[idx],
                                                    self.data))
                t_draw = time.perf_counter()
                with span("chipbench.dispatch"):
                    self.state, h = self.step(self.state, batch)
                t_dispatch = time.perf_counter()
                with span("chipbench.readback"):
                    failed += not np.isfinite(float(h))
                t_end = time.perf_counter()
                round_s.append(t_end - t)
                phases.append((t_draw - t, t_dispatch - t_draw,
                               t_end - t_dispatch))
                rounds += 1
        elapsed = time.perf_counter() - t0
        slowest = sorted(range(rounds), key=lambda i: -round_s[i])[:3]
        return {"rounds": rounds, "elapsed_s": elapsed, "round_s": round_s,
                "failed": failed,
                "slowest": [(i, *(round(1e3 * x, 3) for x in phases[i]))
                            for i in slowest]}

    def flops_per_round(self) -> float:
        return self.family.round_flops(self.cfg["model"], self.cfg["vfl"],
                                       self.B, self.S)

    def release(self):
        self.state = self.data = None

    # ----------------------------------------------------------- check --
    def reference(self, operands="f32", fault=None) -> dict:
        ref = load_module("reference", self.cfg["reference"])
        w0, parties, key = self._start_trees()
        batches = [{k: jnp.asarray(v[idx]) for k, v in
                    self.table_host.items()} for idx in self.check_idx]
        return ref.run(w0, parties, key, batches, self.cfg["model"],
                       self.cfg["vfl"], operands=operands, fault=fault)

    def start_leaves(self) -> dict:
        w0, parties, _ = self._start_trees()
        return chk.named_leaves(w0, parties, self.vfl.num_parties)

    def program_side(self) -> dict:
        return {"h": self.h_prog, "first": self.snaps[0],
                "last": self.snaps[-1]}

    def side_of(self, run: dict) -> dict:
        q = self.vfl.num_parties
        (w1, p1), (w3, p3) = run["states"][0], run["states"][-1]
        return {"h": run["h"], "first": chk.named_leaves(w1, p1, q),
                "last": chk.named_leaves(w3, p3, q)}

    def coefficients(self, last: dict, start: dict, m: list) -> list:
        """[(server, party)] for each round the reference follows: the
        change from ``start`` to ``last`` along the round's server
        direction and along its activated party's (``m``), over -lr.
        The directions are the reference module's draws."""
        ref = load_module("reference", self.cfg["reference"])
        fresh = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
            self.shapes.parties)
        v, out = self.cfg["vfl"], []
        for r, m_r in enumerate(m):
            key = jax.random.fold_in(self._state_key(), r)
            u0 = chk.named("w0/", ref.directions(ref.fold_name(key, "u0"),
                                                 self.shapes.w0))
            server = chk.along(last, start, u0) / -v["lr_server"]
            del u0
            u = chk.named(f"party{m_r}/",
                          ref.directions(ref.fold_name(key, "u"), fresh))
            out.append((server, chk.along(last, start, u) / -v["lr_party"]))
        return out

    def readings(self, side: dict, ref_run: dict, start: dict) -> dict:
        ref = self.side_of(ref_run)
        m0 = ref_run["m"][0]
        first = {k: v for k, v in ref["first"].items()
                 if k.startswith("w0/") or k.startswith(f"party{m0}/")}
        s1 = chk.leaf_stats(side["first"], first, start)
        s3 = chk.leaf_stats(side["last"], ref["last"], start)
        grad, grad_leaf = chk.norm_gap(s1)
        change, change_leaf = chk.norm_gap(s3)
        dir_worst, dir_leaf = chk.dir_gap(s1)
        grad_med, dir_med = chk.median_gaps(s1)
        change_med, _ = chk.median_gaps(s3)
        direction = chk.block_dir_gap(
            {k: v for k, v in s1.items() if k.startswith(f"party{m0}/")})
        server = chk.sign_gap(
            side["first"],
            {k: v for k, v in first.items() if k.startswith("w0/")}, start)

        def coeff_of(leaf, ref_coeff):
            """The program's coefficient, from its change of ``leaf``
            along the shared direction."""
            np_, nr, cos = s1[leaf]
            return (ref_coeff * (np_ / nr) * np.sign(cos) if nr > 0
                    else float("nan"))

        c_prog = self.coefficients(side["last"], start, ref_run["m"])
        c_ref = self.coefficients(ref["last"], start, ref_run["m"])
        value = max([abs(side["h"][0] - ref_run["h"][0])
                     / self.cfg["vfl"]["mu"]]
                    + [abs(a - b) for cp, cr in zip(c_prog, c_ref)
                       for a, b in zip(cp, cr)])
        return {"loss_gap": chk.loss_gap(side["h"], ref_run["h"]),
                "value_gap": value, "grad_gap": grad, "change_gap": change,
                "change_median": change_med, "dir_gap": direction,
                "w0_sign_gap": server,
                "_worst": {"grad": grad_leaf, "change": change_leaf,
                           "dir": [dir_leaf, dir_worst]},
                "_loss0_gap": chk.loss_gap(side["h"][:1], ref_run["h"][:1]),
                "_median": {"grad": grad_med, "dir": dir_med},
                "_coeffs": {"program": c_prog, "reference": c_ref},
                "_coeff_ref": ref_run["coeff"][0],
                "_coeff_prog": float(coeff_of(f"party{m0}/embed",
                                              ref_run["coeff"][0])),
                "_coeff0_ref": ref_run["coeff0"][0],
                "_coeff0_prog": float(coeff_of(self.family.SERVER_LEAF,
                                               ref_run["coeff0"][0]))}


class _NoSpan:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
