"""chip_smoke.py on the CPU: the script refuses to pass without a TPU,
and each of its phases passes at a reduced size. Also the two pieces of
program it leans on: the compile-cache placement and the platform-chosen
kernel mode."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.kernels import platform  # noqa: E402
from repro.utils import compile_cache  # noqa: E402

REDUCED = ["--arch", "qwen1.5-0.5b", "--mode", "vfl-zoo", "--reduced",
           "--parties", "4", "--steps", "3", "--batch-size", "4",
           "--seq-len", "16"]


def _run_script(*args, env=None, cwd=ROOT):
    env = dict(os.environ, **(env or {}))
    return subprocess.run([sys.executable, str(cwd / "chip_smoke.py"),
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


def _ok_line(stdout: str) -> bool:
    return any('"ok": true' in line for line in stdout.splitlines())


def test_script_fails_without_a_tpu():
    out = _run_script(env={"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert not _ok_line(out.stdout)
    assert "platform=cpu" in out.stdout       # phase a reported first


def test_script_fails_outside_the_repo(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    out = _run_script(env=env, cwd=tmp_path)
    assert out.returncode != 0
    assert not _ok_line(out.stdout)


def test_phase_device_refuses_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.phase_device()


@pytest.mark.parametrize("extra", [[], chip_smoke.DEFENDED_ARGV],
                         ids=["plain", "defended"])
def test_phase_trainer_reduced(extra):
    h = chip_smoke.phase_trainer(REDUCED + extra, "reduced",
                                 chip_smoke.CompileMeter())
    assert len(h) == 3


def test_phase_chip_vs_cpu_reduced():
    chip_smoke.phase_chip_vs_cpu(REDUCED)


def test_update_gap_sees_a_step_that_applied_no_update():
    """The losses of a run that never applied its update stay within the
    loss bound; the update gap reads 1 in every block the run moved (w0
    and the parties it activated) and fails the check."""
    from repro.launch import train

    run = train.main(REDUCED)
    h, moves = np.asarray(run.losses), chip_smoke.param_moves(run)
    moved = {k for k, v in moves.items() if np.linalg.norm(v) > 0}
    assert "w0" in moved and len(moved) > 1
    bounds = chip_smoke.CHIP_VS_CPU["highest"]
    chip_smoke._hold("t", "itself", (h, moves), (h, moves), **bounds)
    frozen = {k: np.zeros_like(v) for k, v in moves.items()}
    assert chip_smoke.update_gaps(frozen, moves) == {
        k: float(k in moved) for k in moves}
    with pytest.raises(chip_smoke.SmokeFailure, match="update gap"):
        chip_smoke._hold("t", "no update", (h, frozen), (h, moves),
                         **bounds)


def test_phase_paper_lr_reduced():
    chip_smoke.phase_paper_lr(scale=0.1, steps=500)


def test_phase_kernels_interpreted():
    chip_smoke.phase_kernels(interpret=True, big=False)


def test_phase_data_parallel_on_four_host_devices():
    """--chips 4's phase, on four XLA host devices (they exist only when
    set before JAX starts, hence the child process)."""
    code = ("import sys, chip_smoke; "
            f"chip_smoke.phase_data_parallel({REDUCED!r}, 4)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "on 4 devices, 1 of 4 rows each" in out.stdout


# ---------------------------------------------------------- compile cache --

@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(monkeypatch, restore_cache_dir, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and the
    helper sets nothing."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable_compile_cache() == got     # never moves


def test_compile_cache_refuses_a_place_outside_a_checkout(
        monkeypatch, restore_cache_dir, tmp_path):
    """Imported from an installed package there is no checkout to hold
    the cache: the helper asks for the variable instead of writing into
    the Python installation."""
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    monkeypatch.setattr(compile_cache, "_SRC", tmp_path / "site-packages")
    with pytest.raises(RuntimeError, match=compile_cache.ENV):
        compile_cache.enable_compile_cache()


def test_compile_cache_dir_is_ignored_by_git():
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


# ------------------------------------------------- platform-chosen kernels --

def _wrappers():
    from repro.configs import DPConfig, VFLConfig
    from repro.core.exchange import ZOExchange
    from repro.kernels import fused_round, ops
    from repro.kernels.dual_matmul import dual_matmul_pallas
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.zo_update import zo_update_pallas

    key = jax.random.key(0)
    m = jnp.ones((128, 128), jnp.float32)
    qkv = jnp.ones((1, 128, 2, 64), jnp.float32)
    flat = jnp.linspace(-1.0, 1.0, 300, dtype=jnp.float32)
    bits = jax.random.bits(key, (300,), jnp.uint32)
    dp = DPConfig(noise_multiplier=1.1, clip=0.7)
    ex = ZOExchange.from_config(VFLConfig(
        num_parties=2, mu=1e-3, codec="int8", direction="rademacher",
        dp=dp, fused=True))
    return {
        "ops.dual_matmul": lambda: ops.dual_matmul(m, m, m, mu=1e-2),
        "ops.flash_attention": lambda: ops.flash_attention(qkv, qkv, qkv),
        "ops.zo_update": lambda: ops.zo_update({"w": flat}, {"w": bits},
                                               0.1),
        "dual_matmul_pallas": lambda: dual_matmul_pallas(m, m, m, mu=1e-2),
        "flash_attention_pallas": lambda: flash_attention_pallas(
            qkv[0].transpose(1, 0, 2), qkv[0].transpose(1, 0, 2),
            qkv[0].transpose(1, 0, 2)),
        "zo_update_pallas": lambda: zo_update_pallas(flat, bits,
                                                     jnp.float32(0.1)),
        "defended_encode": lambda: fused_round.defended_encode(
            flat, bits, bits, dp, "int8", impl="pallas"),
        "encode_up_fused": lambda: fused_round.encode_up_fused(
            ex, flat, key, impl="pallas"),
        "roundtrip_up_fused": lambda: fused_round.roundtrip_up_fused(
            ex, flat, key, impl="pallas"),
        "zo_apply": lambda: fused_round.zo_apply(
            {"w": flat}, key, jnp.float32(0.1), impl="pallas"),
        "perturb": lambda: fused_round.perturb({"w": flat}, key, 1e-3,
                                               impl="pallas"),
        "ZOExchange.apply_fused": lambda: ex.apply_fused(
            {"w": flat}, key, jnp.float32(0.5), 1e-2),
    }


WRAPPERS = ("ops.dual_matmul", "ops.flash_attention", "ops.zo_update",
            "dual_matmul_pallas", "flash_attention_pallas",
            "zo_update_pallas", "defended_encode", "encode_up_fused",
            "roundtrip_up_fused", "zo_apply", "perturb",
            "ZOExchange.apply_fused")


def test_wrapper_list_is_complete():
    assert tuple(_wrappers()) == WRAPPERS


@pytest.mark.parametrize("name", WRAPPERS)
def test_kernel_wrapper_interprets_on_cpu_by_default(name, monkeypatch):
    """No interpret= passed: on the CPU every wrapper resolves to the
    interpreter (a compiled Mosaic kernel cannot run here) and finishes."""
    seen = []
    resolve = platform.interpret_mode

    def spy(interpret=None):
        seen.append(interpret)
        return resolve(interpret)

    for mod in _kernel_modules():
        monkeypatch.setattr(mod, "interpret_mode", spy)
    jax.clear_caches()          # retrace, so the spy sees every kernel
    out = _wrappers()[name]()
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in jax.tree.leaves(out))
    assert seen and all(v is None for v in seen)
    assert platform.interpret_mode() is True


def _kernel_modules():
    from repro.kernels import dual_matmul, flash_attention, fused_round
    from repro.kernels import zo_update
    return (dual_matmul, flash_attention, fused_round, zo_update)


def test_interpret_mode_follows_the_target_platform(monkeypatch):
    assert platform.interpret_mode() is True
    with jax.default_device(jax.devices("cpu")[0]):
        assert platform.target_platform() == "cpu"
    monkeypatch.setattr(platform.jax, "default_backend", lambda: "tpu")
    assert platform.target_platform() == "tpu"
    assert platform.interpret_mode() is False
    assert platform.interpret_mode(True) is True       # explicit wins
    with jax.default_device(jax.devices("cpu")[0]):
        assert platform.interpret_mode() is True
