"""The phases of an AsyREVEL round are named on the device.

``core/asyrevel.py``, ``core/exchange.py`` and the models' stale read
(``core/vfl.py``) name Algorithm 1's steps with ``jax.named_scope``, and
the models name their DeepSeek-V2 layers (``models/attention.py``,
``models/moe.py``); the chip benchmark reads each device op's phase
from the op_name that XLA keeps in the compiled program
(``chipbench/scopes.py``). Each name it reads must reach the compiled HLO
of the two stepping paths it measures, the vfl-zoo step and the sharded
LR scan, so that renaming a scope in the program fails here and not
silently on the chip.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import scopes  # noqa: E402
from repro.configs import PaperLRConfig, VFLConfig  # noqa: E402
from repro.core import asyrevel  # noqa: E402
from repro.core.vfl import PaperLRModel  # noqa: E402


def _phases(compiled) -> set:
    ops = scopes.op_names_of(compiled.as_text()).values()
    return {scopes.scope_of(op) for op in ops}


def _zoo_step(codec="bf16", arch="qwen1.5-0.5b"):
    from repro.configs import get_config
    from repro.launch import steps as step_lib
    from repro.models import build_model
    vfl = VFLConfig(num_parties=2, party_hidden=16, max_delay=2, mu=1e-3,
                    lr_party=1e-3, lr_server=1e-3, codec=codec)
    model = build_model(get_config(arch, reduced=True))
    _, init, step = step_lib.make_vfl_zoo_step(model, vfl)
    return init, step


def _lr_scan(n=64, d=16, q=4, batch=8):
    vfl = VFLConfig(num_parties=q, mu=1e-3, lr_party=1e-2, lr_server=1e-3,
                    max_delay=2, lam=1e-4)
    model = PaperLRModel(PaperLRConfig(num_features=d, num_parties=q))
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    fn = asyrevel.make_sharded_train_fn(model, vfl, n, batch, mesh=mesh)
    state = asyrevel.init_state(model, vfl, jax.random.key(0))
    return fn, state


def test_zoo_step_names_every_phase_but_the_gather():
    # bf16 up-link: an f32 codec without DP is the identity, and leaves
    # exchange_up with no instruction to name
    init, step = _zoo_step()
    state = jax.eval_shape(init, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 8), jnp.int32)
             for k in ("tokens", "targets")}
    got = _phases(jax.jit(step).lower(state, batch).compile())
    assert set(scopes.SCOPES) - {"batch_gather"} <= got


def test_zoo_step_names_the_deepseek_v2_layers():
    """The benchmark's mla_ms and moe_*_ms metrics read these scopes
    (chipbench/metrics/)."""
    init, step = _zoo_step(arch="deepseek-v2-lite")
    state = jax.eval_shape(init, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 8), jnp.int32)
             for k in ("tokens", "targets")}
    ops = scopes.op_names_of(jax.jit(step).lower(state, batch).compile()
                             .as_text()).values()
    names = {w for op in ops for w in scopes._WORD.findall(op)}
    assert {"mla", "moe_dispatch", "moe_experts", "moe_shared"} <= names


def test_lr_scan_names_every_phase_but_the_codec():
    fn, state = _lr_scan()
    keys = jax.random.split(jax.random.key(1), 3)
    data = {"x": jax.ShapeDtypeStruct((64, 16), jnp.float32),
            "y": jax.ShapeDtypeStruct((64,), jnp.float32)}
    got = _phases(fn.lower(state, keys, data).compile())
    assert set(scopes.SCOPES) - {"exchange_up"} <= got


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/zo_update/scatter-add", "zo_update"),
    ("jit(step)/zo_update/jvp(server_forward)/dot_general",
     "server_forward"),
    ("jit(f)/shard_map/while/body/batch_gather/gather", "batch_gather"),
    ("jit(step)/vmap(party_forward)/tanh", "party_forward"),
    ("jit(step)/copy", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_scope_of_takes_the_innermost_phase(op_name, phase):
    assert scopes.scope_of(op_name) == phase

