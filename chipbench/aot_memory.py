"""Compile a zoo_step cell's step for a described TPU v5e, with no chip,
and print what its compiled memory analysis says a chip would hold.

  JAX_PLATFORMS=cpu python chipbench/aot_memory.py --workload NAME \
      [--batch B]

The state and batch are shapes only (``jax.eval_shape``); with a mesh of
4 the arguments carry NamedShardings over four described chips. This
sizes a cell's batch before any chip run; it measures nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[0] = str(Path(__file__).resolve().parent.parent)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--batch", type=int, default=None)
    args = p.parse_args(argv)
    from chipbench import common
    common.use_checkout_cache()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    jax.config.update("jax_enable_compilation_cache", False)
    from chipbench.drivers.zoo_step import vfl_config
    from repro.launch import steps as step_lib
    from repro.models import build_model

    spec = common.cell_spec(args.workload)
    cfg, traffic = spec["config"], spec["traffic"]
    B = args.batch or traffic["batch"]
    S, n = traffic["seq"], traffic["mesh"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(topo.devices[:n], ("data",))
    _, init, step = step_lib.make_vfl_zoo_step(
        build_model(common.load_module("families", cfg["family"])
                    .program_config(cfg)), vfl_config(cfg),
        mesh=mesh if n > 1 else None)
    rep = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(init, jax.random.key(0)))
    bsh = NamedSharding(mesh, P("data")) if n > 1 else rep
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh)
             for k in ("tokens", "targets")}
    compiled = jax.jit(step).lower(state, batch).compile()
    m = compiled.memory_analysis()
    out = {"workload": args.workload, "batch": B, "seq": S, "chips": n}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes"):
        out[k] = getattr(m, k, None)
    out["total_gb"] = (out["argument_size_in_bytes"]
                       + out["output_size_in_bytes"]
                       - out["alias_size_in_bytes"]
                       + out["temp_size_in_bytes"]) / 1e9
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
