"""Mixture-of-Experts FFN with top-k routing and capacity-based dispatch.

Dispatch strategy (TPU/SPMD-friendly, see DESIGN.md §7): tokens are scattered
into a dense (E, C, d) buffer via computed positions (cumsum of one-hot
assignments), experts run as one batched einsum over the expert axis — which
shards cleanly over the mesh 'model' axis (expert parallelism) — and results
are gathered back weighted by the router gates. Tokens beyond an expert's
capacity C = ceil(N*top_k/E * capacity_factor) are dropped (standard
Switch/GShard semantics); the router aux loss pushes the load toward balance.

The dropless layer (``MoEConfig.dispatch == "dropless"``, DeepSeek-V2's
DeepseekV2MoE) is one chip's share under expert parallelism: it holds
experts [first, first + held) of the routed ones, routes every token over
all of them in f32, and computes its held experts' part of the result for
every (token, slot) pair routed to them, plus the shared experts. Pairs
are sorted by expert and cut into row tiles of one expert each; a tile
loop gathers each tile's tokens and runs the expert's SwiGLU on them, and
each token then sums its slots' weighted rows, so the matmuls grow with
the tokens routed here, not with a capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, swiglu_apply, swiglu_init
from repro.sharding.ctx import constrain, current_mesh


def _cumsum_groups(n: int) -> int:
    """Group count for the hierarchical dispatch cumsum: at least the data
    shard count (so the inner scan never crosses shards), capped at 256,
    and dividing n."""
    mesh = current_mesh()
    base = 1
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        base = sizes.get("pod", 1) * sizes.get("data", 1)
    g = max(base, 16)
    while g > 1 and n % g:
        g //= 2
    return max(g, 1)


def moe_init(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    m = cfg.moe
    E = m.held                    # the router still scores all experts
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], d, m.num_experts, dtype, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (E, d, m.d_ff_expert),
                                     jnp.float32) / np.sqrt(d)).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, m.d_ff_expert),
                                   jnp.float32) / np.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, m.d_ff_expert, d),
                                     jnp.float32)
                   / np.sqrt(m.d_ff_expert)).astype(dtype),
    }
    if m.num_shared_experts:
        p["shared"] = swiglu_init(jax.random.fold_in(key, 4), d,
                                  m.num_shared_experts * m.d_ff_expert, dtype)
    return p


def moe_apply(p, cfg: ModelConfig, x):
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar)."""
    if cfg.moe.dispatch == "dropless":
        return moe_dropless_apply(p, cfg, x)
    B, S, d = x.shape
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    N = B * S
    xf = x.reshape(N, d)
    logits = jnp.dot(xf, p["router"]).astype(jnp.float32)      # (N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # (N,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)       # renormalize

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    dense_mask = jax.nn.one_hot(expert_idx, E).sum(axis=1)      # (N,E)
    f = jnp.mean(dense_mask, axis=0)
    P = jnp.mean(probs, axis=0)
    aux = m.router_aux_coef * E * jnp.sum(f * P)

    C = int(np.ceil(N * K / E * m.capacity_factor))
    C = max(C, 4)
    # position of each (token, slot) within its expert queue.
    # A flat cumsum over the (N*K, E) one-hot would scan along the
    # data-sharded token dim and force GSPMD to all-gather the whole
    # matrix (4.3 GB/layer at 32k prefill — §Perf D1). Instead: grouped
    # hierarchical cumsum — local scan within shard-aligned groups plus a
    # tiny (G, E) cross-group offset scan.
    flat_idx = expert_idx.reshape(-1)                           # (N*K,)
    G = _cumsum_groups(N * K)
    oh_g = jax.nn.one_hot(flat_idx.reshape(G, -1), E,
                          dtype=jnp.int32)                      # (G,n,E)
    local = jnp.cumsum(oh_g, axis=1) - oh_g                     # local scan
    group_tot = jnp.sum(oh_g, axis=1)                           # (G,E)
    offsets = jnp.cumsum(group_tot, axis=0) - group_tot         # (G,E)
    pos_in_e = (local + offsets[:, None, :]).reshape(N * K, E)
    onehot = oh_g.reshape(N * K, E)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)                   # (N*K,)
    keep = pos < C
    gate_flat = gate_vals.reshape(-1) * keep

    # scatter tokens into (E, C, d)
    buf = jnp.zeros((E, C, d), x.dtype)
    tok_ids = jnp.repeat(jnp.arange(N), K)
    safe_pos = jnp.where(keep, pos, C - 1)
    buf = buf.at[flat_idx, safe_pos].add(
        (xf[tok_ids] * keep[:, None]).astype(x.dtype),
        mode="drop")

    # expert computation: batched swiglu over the expert axis
    # (expert-parallel: the E dim lives on the mesh 'model' axis)
    buf = constrain(buf, ("model", None, None))
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])              # (E,C,d)

    # gather back, weighted by gates
    out_flat = y[flat_idx, safe_pos] * gate_flat[:, None].astype(x.dtype)
    out = jnp.zeros((N, d), x.dtype).at[tok_ids].add(out_flat)
    return out.reshape(B, S, d), aux


# ------------------------------------------------------------ dropless ---

TILE = 256    # rows of one expert a grouped-matmul step computes


def route(p, cfg: ModelConfig, xf):
    """DeepSeek's MoEGate: f32 logits over every routed expert, softmax,
    greedy top-k, the top-k probabilities not renormalized
    (norm_topk_prob false, routed_scaling_factor 1). Returns (weights
    (N,K) f32, experts (N,K) int)."""
    m = cfg.moe
    logits = jnp.dot(xf.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)


def moe_dropless_apply(p, cfg: ModelConfig, x):
    """x: (B,S,d) -> (shared + this share's routed part (B,S,d), 0): the
    layer adds no balance loss (MoEConfig refuses a router_aux_coef)."""
    B, S, d = x.shape
    m = cfg.moe
    E, K, N = m.held, m.top_k, B * S
    xf = x.reshape(N, d)
    with jax.named_scope("moe_dispatch"):
        w, idx = route(p, cfg, xf)
        local = idx.reshape(-1) - m.first_expert               # (N*K,)
        held = (local >= 0) & (local < E)
        key = jnp.where(held, local, E)     # pairs of absent experts: last
        order = jnp.argsort(key, stable=True)                  # (N*K,)
        counts = jnp.sum(jax.nn.one_hot(key, E + 1, dtype=jnp.int32),
                         axis=0)[:E]
        row0 = jnp.cumsum(counts) - counts     # first sorted row of each
        tiles = (counts + TILE - 1) // TILE
        tile_end = jnp.cumsum(tiles)
        tile0 = tile_end - tiles                # first tile of each expert
        n_tiles = tile_end[-1]
        # the most tiles any routing can need: every pair held, each
        # expert's last tile part full
        max_tiles = -(-N * min(K, E) // TILE) + E
        t_ids = jnp.arange(max_tiles)
        t_expert = jnp.minimum(
            jnp.searchsorted(tile_end, t_ids, side="right"), E - 1)
        # each expert's rows start on a tile of their own: a pair's row
        # in the tiles' output is its expert's first tile plus its rank
        k_sorted = key[order]
        e_sorted = jnp.minimum(k_sorted, E - 1)
        at = tile0[e_sorted] * TILE + jnp.arange(N * K) - row0[e_sorted]
        pos = jnp.zeros(N * K, jnp.int32).at[order].set(
            jnp.where(k_sorted < E, at, 0)).reshape(N, K)
        w = jnp.where(held.reshape(N, K), w, 0.0)

    def tile(t):
        e = t_expert[t]
        with jax.named_scope("moe_dispatch"):
            within = (t - tile0[e]) * TILE + jnp.arange(TILE)
            rows = jnp.minimum(row0[e] + within, N * K - 1)
            xt = xf[order[rows] // K]
        with jax.named_scope("moe_experts"):
            g = jnp.dot(xt, p["w_gate"][e])
            u = jnp.dot(xt, p["w_up"][e])
            return jnp.dot(jax.nn.silu(g) * u, p["w_down"][e])

    def step(_, t):
        # a scan over the static bound keeps the layer differentiable;
        # tiles past this routing's count are skipped
        return None, jax.lax.cond(
            t < n_tiles, tile, lambda t: jnp.zeros((TILE, d), x.dtype), t)

    _, ys = jax.lax.scan(step, None, t_ids)
    with jax.named_scope("moe_dispatch"):
        # one slot's rows at a time, summed in f32 as they come: no
        # (N, K, d) value, whose slot axis a TPU tile pads from 6 to 8
        ys = ys.reshape(max_tiles * TILE, d)
        out = ys[pos[:, 0]].astype(jnp.float32) * w[:, 0, None]
        for k in range(1, K):
            out = out + ys[pos[:, k]].astype(jnp.float32) * w[:, k, None]
        out = out.astype(x.dtype)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            out = out + swiglu_apply(p["shared"], xf)
    return out.reshape(B, S, d), jnp.zeros((), jnp.float32)
