"""Kernel bench: the fused defended-round hot path, measured.

Three sections:

  1. Fused release ops (kernels/fused_round) — wall-clock of the fused
     single-dispatch path vs the unfused eager oracle chain for every
     codec x DP combination, with BITWISE parity asserted on the spot
     (a fused path that drifts from the seam it replaces is a bug, not
     a tradeoff — docs/kernels.md).
  2. End-to-end rounds — HostAsyncTrainer.run_serial wall-clock per
     round across (codec, dp, fused). The acceptance row is the ISSUE
     criterion: the FUSED DEFENDED round (DP on, int8 wire) must land
     within 1.05x of the UNFUSED UNDEFENDED round — privacy at
     (approximately) the price of the plain protocol.
  3. The standalone kernels (dual matmul / flash attention / zo update)
     + the TPU traffic model they optimize. They compile on a TPU and
     interpret elsewhere (kernels/platform.py; the artifact records the
     platform); their wall-clock is correctness timing only, never a
     perf claim.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import DPConfig, PaperLRConfig, VFLConfig
from repro.core.async_host import HostAsyncTrainer
from repro.core.exchange import ZOExchange
from repro.core.vfl import PaperLRModel, pad_features
from repro.kernels import fused_round, ops, ref


def _time(f, *args, n=3):
    f(*args)                      # compile/warm
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / n * 1e6


def _wires_equal(a, b) -> bool:
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


_DP = DPConfig(noise_multiplier=1.3, clip=1.0)


def _ex(codec, dp, fused, K=1):
    # rademacher directions so the seed-replay fused ops are in play
    return ZOExchange.from_config(VFLConfig(
        num_parties=4, mu=1e-3, codec=codec, num_directions=K,
        direction="rademacher", dp=dp, fused=fused))


def fused_op_rows():
    """Section 1: per-op fused-vs-unfused sweep on a release-sized array."""
    rows = []
    key = jax.random.key(7)
    c = jax.random.normal(jax.random.fold_in(key, 0), (8, 4096))
    for codec in ("f32", "bf16", "int8"):
        for dp in (None, _DP):
            tag = f"{codec}_{'dp' if dp is not None else 'nodp'}"
            ex_u = _ex(codec, dp, fused=False)
            ex_f = _ex(codec, dp, fused=True)
            us_u = _time(lambda: ex_u.encode_up(c, key), n=10)
            us_f = _time(lambda: ex_f.encode_up(c, key), n=10)
            same = _wires_equal(ex_u.encode_up(c, key),
                                ex_f.encode_up(c, key))
            assert same, f"fused encode_up diverged for {tag}"
            rows.append((f"fused_encode_up_{tag}", us_f,
                         f"unfused_us={us_u:.1f};speedup={us_u / us_f:.2f};"
                         f"parity=bitwise"))
    # the pallas path (interpret on CPU; compiled on TPU) — same math,
    # validated bitwise against the same oracle on a smaller block
    c_small = c[:, :512]
    ex_u = _ex("int8", _DP, fused=False)
    wire_p = fused_round.encode_up_fused(_ex("int8", _DP, fused=True),
                                         c_small, key, impl="pallas")
    same = _wires_equal(ex_u.encode_up(c_small, key), wire_p)
    assert same, "pallas encode_up diverged from the unfused oracle"
    us_p = _time(lambda: fused_round.encode_up_fused(
        _ex("int8", _DP, fused=True), c_small, key, impl="pallas"), n=3)
    rows.append(("fused_encode_up_pallas_int8_dp", us_p,
                 "parity=bitwise;note=correctness timing"))

    # perturb + apply_direction: the party-side fused ops
    w = {"w": jax.random.normal(jax.random.fold_in(key, 1), (1 << 16,))}
    ex_u = _ex("f32", None, fused=False)
    us_u = _time(lambda: ex_u.perturb(w, key), n=10)
    us_f = _time(lambda: fused_round.perturb(w, key, ex_u.mu), n=10)
    p_u, u_u = ex_u.perturb(w, key)
    p_f, u_f = fused_round.perturb(w, key, ex_u.mu)
    assert _wires_equal(p_u, p_f) and _wires_equal(u_u, u_f)
    rows.append(("fused_perturb", us_f,
                 f"unfused_us={us_u:.1f};speedup={us_u / us_f:.2f};"
                 f"parity=bitwise"))
    coeff, lr = np.float32(0.37), 1e-2
    us_u = _time(lambda: ex_u.apply_direction(w, u_u, coeff, lr), n=10)
    us_f = _time(lambda: fused_round.apply_direction_fused(
        w, u_u, coeff, lr), n=10)
    assert _wires_equal(ex_u.apply_direction(w, u_u, coeff, lr),
                        fused_round.apply_direction_fused(w, u_u, coeff, lr))
    rows.append(("fused_apply_direction", us_f,
                 f"unfused_us={us_u:.1f};speedup={us_u / us_f:.2f};"
                 f"parity=bitwise"))
    return rows


def _round_once(model, X, y, codec, dp, fused, K=1, rounds=40, batch=64):
    """One fresh serial run; returns (us/round, result). GC is paused
    for the timed region — collector pauses land on whichever config is
    running and would otherwise dominate the sub-ms deltas measured
    here."""
    vfl = VFLConfig(num_parties=4, mu=1e-3, lr_party=1e-2,
                    lr_server=1e-3, codec=codec, num_directions=K,
                    direction="rademacher", dp=dp, fused=fused)
    tr = HostAsyncTrainer(model, vfl, X, y, batch_size=batch,
                          compute_cost_s=0.0, seed=0)
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        res = tr.run_serial(rounds)
        return (time.perf_counter() - t0) / rounds * 1e6, res
    finally:
        gc.enable()


def round_rows():
    """Section 2: end-to-end serial rounds + the 1.05x acceptance row."""
    q, d, n = 4, 64, 512
    model = PaperLRModel(PaperLRConfig(num_features=d, num_parties=q))
    key = jax.random.key(0)
    X = np.asarray(pad_features(jax.random.normal(key, (n, d)), d, q))
    y = np.asarray(jnp.sign(jax.random.normal(
        jax.random.fold_in(key, 1), (n,))))

    rows = []
    grid = [("unfused_f32", "f32", None, False, 1),
            ("fused_f32", "f32", None, True, 1),
            ("unfused_dp_int8", "int8", _DP, False, 1),
            ("fused_dp_int8", "int8", _DP, True, 1),
            ("unfused_dp_int8_K3", "int8", _DP, False, 3),
            ("fused_dp_int8_K3", "int8", _DP, True, 3)]
    # warm every config's jit caches first, then INTERLEAVE the timed
    # repeats across the grid and keep per-config minimums — the rounds
    # here are dispatch-bound (~10µs ops on (batch,) payloads), so slow
    # scheduler/allocator drift over the sweep would otherwise bias
    # whichever config happens to run last
    us = {}
    h = {}
    for tag, codec, dp, fused, K in grid:
        _, res = _round_once(model, X, y, codec, dp, fused, K=K)
        h[tag] = float(res.history[-1][1]) if res.history else float("nan")
    for _ in range(3):
        for tag, codec, dp, fused, K in grid:
            t, _res = _round_once(model, X, y, codec, dp, fused, K=K)
            us[tag] = min(us.get(tag, float("inf")), t)
    for tag, *_cfg in grid:
        rows.append((f"round_serial_{tag}", us[tag], f"h_final={h[tag]:.6f}"))
    # fused-vs-unfused parity at the run level (same config, fused off/on)
    for a, b in (("unfused_dp_int8", "fused_dp_int8"),
                 ("unfused_dp_int8_K3", "fused_dp_int8_K3")):
        assert h[a] == h[b], f"fused run diverged from unfused: {a} vs {b}"
    # THE acceptance criterion: defended fused round within 1.05x of the
    # undefended unfused round
    ratio = us["fused_dp_int8"] / us["unfused_f32"]
    rows.append(("round_fused_defended_vs_unfused_undefended",
                 us["fused_dp_int8"],
                 f"baseline_us={us['unfused_f32']:.1f};ratio={ratio:.3f};"
                 f"threshold=1.05;pass={int(ratio <= 1.05)}"))
    # the like-for-like fused win on the defended config
    rows.append(("round_fused_speedup_dp_int8", us["fused_dp_int8"],
                 f"unfused_us={us['unfused_dp_int8']:.1f};"
                 f"speedup={us['unfused_dp_int8'] / us['fused_dp_int8']:.2f};"
                 f"parity=run_bitwise"))
    return rows


def legacy_rows():
    """Section 3: the standalone kernels + traffic model. The references'
    XLA matmuls run at HIGHEST precision, as the kernels' f32-accumulated
    products do; a TPU's default one-pass bf16 matmul would not."""
    rows = []
    key = jax.random.key(0)
    M, K, N = 256, 1024, 512
    x = jax.random.normal(jax.random.fold_in(key, 1), (M, K))
    w = jax.random.normal(jax.random.fold_in(key, 2), (K, N))
    u = jax.random.normal(jax.random.fold_in(key, 3), (K, N))
    us = _time(lambda: ops.dual_matmul(x, w, u, mu=1e-3))
    naive_bytes = 2 * (M * K + K * N) * 4 + 2 * M * N * 4
    fused_bytes = (M * K + 2 * K * N) * 4 + 2 * M * N * 4
    seedreplay_bytes = (M * K + K * N) * 4 + 2 * M * N * 4
    rows.append(("kernel_dual_matmul", us,
                 f"naiveB={naive_bytes};fusedB={fused_bytes};"
                 f"seedreplayB={seedreplay_bytes};"
                 f"traffic_saving={1-fused_bytes/naive_bytes:.2%}"))
    y0, y1 = ops.dual_matmul(x, w, u, mu=1e-3)
    with jax.default_matmul_precision("highest"):
        r0, r1 = ref.dual_matmul_ref(x, w, u, mu=1e-3)
    err = float(jnp.max(jnp.abs(y1 - r1)))
    rows.append(("kernel_dual_matmul_maxerr", 0.0, f"err={err:.2e}"))

    B, S, H, hd = 1, 256, 4, 64
    q = jax.random.normal(jax.random.fold_in(key, 4), (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 5), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 6), (B, S, H, hd))
    us = _time(lambda: ops.flash_attention(q, k, v, causal=True))
    o = ops.flash_attention(q, k, v, causal=True)
    with jax.default_matmul_precision("highest"):
        o_ref = ref.flash_attention_ref(
            q.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
            k.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
            v.transpose(0, 2, 1, 3).reshape(B * H, S, hd), causal=True
        ).reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    err = float(jnp.max(jnp.abs(o - o_ref)))
    vmem = (128 * hd * 3 + 128 * 128) * 4
    rows.append(("kernel_flash_attention", us,
                 f"err={err:.2e};vmem_tile_bytes={vmem};"
                 f"quadratic_hbm_avoided={(S*S*H*4)}"))

    w_ = jax.random.normal(jax.random.fold_in(key, 7), (1 << 16,))
    bits = jax.random.bits(jax.random.fold_in(key, 8), (1 << 16,),
                           jnp.uint32)
    us = _time(lambda: ops.zo_update({"w": w_}, {"w": bits}, 0.01))
    n = w_.size
    materialized = 3 * n * 4          # read w, read u(f32), write w
    seedreplay = 2 * n * 4            # read w, write w (bits on-chip PRNG)
    rows.append(("kernel_zo_update", us,
                 f"materializedB={materialized};seedreplayB={seedreplay};"
                 f"traffic_saving={1-seedreplay/materialized:.2%}"))
    return rows


def run():
    return fused_op_rows() + round_rows() + legacy_rows()


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
