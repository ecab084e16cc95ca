"""Driver ``scan``: the program's device scan trainer
(``core/asyrevel.make_sharded_train_fn``) over the paper's LR model, on a
1-D data mesh of the cell's chips. One call runs ``rounds_per_dispatch``
rounds, each drawing its batch rows on the device from its own key; the
losses of every call are read back before the next.

Set-up makes the data table on the device from the seed, builds one
trainer and one state, and runs the first call (which compiles); the
reference follows that call's rounds once the window has closed. The
losses of the first ``check_rounds`` rounds are compared; the state,
which the scan shows only between calls, after the whole first call.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check as chk
from chipbench.common import load_module, seed_key
from chipbench.traffic_gen import classification_table
from chipbench.weights import fill


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices = devices
        self.data_cfg, self.v = cfg["data"], dict(cfg["vfl"])
        self.B = traffic["batch"]
        self.v["batch"] = self.B
        self.R = traffic["rounds_per_dispatch"]
        self.n_check = traffic["check_rounds"]
        self.q = self.v["num_parties"]
        self.precision = cfg["matmul_precision"]
        self.family = load_module("families", cfg["family"])

    def _start(self):
        key = seed_key(self.seed)
        shapes = {"b": jax.ShapeDtypeStruct((), jnp.float32),
                  "w": jax.ShapeDtypeStruct(
                      (self.q, self.data_cfg["features"] // self.q),
                      jnp.float32)}
        st = fill(shapes, jax.random.fold_in(key, 1),
                  self.cfg["init_std"], kinds={"b": "zeros"})
        return st["b"], st["w"], jax.random.fold_in(key, 3)

    def _keys(self, j):
        return self._make_keys(jax.random.fold_in(seed_key(self.seed), 4), j)

    def setup(self):
        from repro.configs import VFLConfig
        from repro.core import asyrevel
        from repro.launch.mesh import make_data_mesh

        t0 = time.perf_counter()
        d, n = self.data_cfg["features"], self.data_cfg["rows"]
        assert d % self.q == 0, "features must split evenly over q"
        v = self.v
        self.vfl = VFLConfig(num_parties=self.q, mu=v["mu"],
                             lr_party=v["lr_party"],
                             lr_server=v["lr_server"],
                             max_delay=v["max_delay"],
                             direction=v["direction"], codec=v["codec"],
                             lam=v["lam"], fused=v["fused"])
        model = self.family.program_model(self.cfg)
        mesh = make_data_mesh(self.traffic["mesh"])
        with jax.default_matmul_precision(self.precision):
            self.fn = asyrevel.make_sharded_train_fn(
                model, self.vfl, n, self.B, mesh=mesh)
        self.data = classification_table(
            jax.random.fold_in(seed_key(self.seed), 5), n, d,
            self.data_cfg["block_rows"], self.data_cfg["noise"])
        b, w, key = self._start()
        tau = self.vfl.max_delay
        hist = {"w": jnp.broadcast_to(w[None], (tau + 1,) + w.shape)}
        from repro.sharding.rules import replicated_pspecs, shard_tree
        # placed where the trainer keeps them, so that the first call
        # compiles the program every later call runs
        state = asyrevel.AsyState({"b": b}, {"w": w}, hist,
                                  jnp.zeros((), jnp.int32), key)
        self.state = shard_tree(state, mesh, replicated_pspecs(state))
        self.data = shard_tree(self.data, mesh, replicated_pspecs(self.data))
        self._make_keys = jax.jit(
            lambda k, j: jax.random.split(jax.random.fold_in(k, j), self.R))
        self.dispatches = 0
        t = time.perf_counter()
        self.phases = {"state and table": t - t0}
        self.state, losses = self._call()
        self.h_prog = [float(x) for x in np.asarray(losses)]
        self.phases["call 0"] = time.perf_counter() - t
        t = time.perf_counter()
        self.snap = {"b": np.asarray(self.state.w0["b"]),
                     "w": np.asarray(self.state.parties["w"])}
        self.snap_s = time.perf_counter() - t

    def _call(self):
        keys = self._keys(self.dispatches)
        self.dispatches += 1
        with jax.default_matmul_precision(self.precision):
            return self.fn(self.state, keys, self.data)

    def window(self, seconds: float, annotate: bool = False) -> dict:
        from chipbench.drivers.zoo_step import _NoSpan
        span = jax.profiler.TraceAnnotation if annotate else _NoSpan
        rounds, failed = 0, 0
        t0 = time.perf_counter()
        with span("chipbench.window"):
            while time.perf_counter() - t0 < seconds:
                with span("chipbench.dispatch"):
                    self.state, losses = self._call()
                with span("chipbench.readback"):
                    losses = np.asarray(losses)
                failed += int(np.sum(~np.isfinite(losses)))
                rounds += self.R
        return {"rounds": rounds, "elapsed_s": time.perf_counter() - t0,
                "failed": failed}

    def flops_per_round(self) -> float:
        return self.family.round_flops(self.data_cfg, self.cfg["vfl"],
                                       self.B)

    def release(self):
        self.state = None

    # ----------------------------------------------------------- check --
    def reference(self, operands="f32", fault=None) -> dict:
        ref = load_module("reference", self.cfg["reference"])
        b, w, key = self._start()
        return ref.run(b, w, key, self._keys(0), self.data, self.v,
                       operands=operands, fault=fault)

    def _named(self, b, w) -> dict:
        out = {"w0/b": b}
        out.update({f"party{j}/w": (j, w) for j in range(self.q)})
        return out

    def start_leaves(self) -> dict:
        b, w, _ = self._start()
        return self._named(b, w)

    def program_side(self) -> dict:
        return {"h": self.h_prog,
                "last": self._named(self.snap["b"], self.snap["w"])}

    def side_of(self, run: dict) -> dict:
        return {"h": run["h"], "last": self._named(*run["state"])}

    def readings(self, side: dict, ref_run: dict, start: dict) -> dict:
        ref = self.side_of(ref_run)
        s = chk.leaf_stats(side["last"], ref["last"], start)
        change, change_leaf = chk.norm_gap(s)
        dir_worst, dir_leaf = chk.dir_gap(s)
        change_med, _ = chk.median_gaps(s)
        k = self.n_check
        return {"loss_gap": chk.loss_gap(side["h"][:k], ref_run["h"][:k]),
                "change_gap": change, "change_median": change_med,
                "dir_gap": chk.block_dir_gap(s),
                "_loss_gap_call": chk.loss_gap(side["h"], ref_run["h"]),
                "_worst": {"change": change_leaf,
                           "dir": [dir_leaf, dir_worst]}}
