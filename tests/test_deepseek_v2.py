"""DeepSeek-V2-Lite in the program: latent attention (MLA) with YaRN rope,
the dropless expert layer that holds one chip's share of the routed
experts, the dense first layer, and the untied vfl-zoo server. Each part
is compared with a plain f32 computation at a tiny size on the CPU; the
whole server loss with the chip benchmark's reference
(chipbench/reference/deepseek_v2_zoo.py).

Tolerances: every comparison is f32 against f32 (the CPU computes an f32
matmul in f32; the plain sides run at HIGHEST), so they differ only by
summation order, a few ulps of the largest term: 1e-5 relative, or 2e-5
absolute on outputs of order 1."""
import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from repro.configs import VFLConfig, get_config  # noqa: E402
from repro.core.vfl import TransformerVFLModel  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.layers import swiglu_apply  # noqa: E402

HI = jax.lax.Precision.HIGHEST


def test_config_has_the_published_sizes():
    c = get_config("deepseek-v2-lite")
    assert (c.num_layers, c.d_model, c.num_heads, c.d_ff, c.vocab_size,
            c.first_k_dense, c.tie_embeddings, c.norm_eps,
            c.rope_theta) == (27, 2048, 16, 10944, 102400, 1, False, 1e-6,
                              10000.0)
    a = c.mla
    assert (a.kv_lora_rank, a.qk_nope_head_dim, a.qk_rope_head_dim,
            a.v_head_dim, a.rope_factor, a.original_max_position,
            a.beta_fast, a.beta_slow, a.mscale,
            a.mscale_all_dim) == (512, 128, 64, 128, 40.0, 4096, 32.0, 1.0,
                                  0.707, 0.707)
    m = c.moe
    assert (m.num_experts, m.top_k, m.d_ff_expert, m.num_shared_experts,
            m.held, m.router_aux_coef, m.dispatch) == (64, 6, 1408, 2, 64,
                                                       0.0, "dropless")
    assert c.citation == "hf:deepseek-ai/DeepSeek-V2-Lite"
    with pytest.raises(ValueError, match="no balance loss"):
        dataclasses.replace(m, router_aux_coef=0.01)
    # the reduced variant keeps the kinds of layer
    r = get_config("deepseek-v2-lite", reduced=True)
    assert r.mla is not None and r.first_k_dense == 1 and r.num_layers == 2
    assert r.moe.dispatch == "dropless" and r.moe.num_shared_experts == 2


def test_yarn_frequencies_and_scale():
    """Published sizes: the correction dims of beta_fast 32 and beta_slow
    1 are 10 and 23, so the first 10 frequencies extrapolate, those from
    23 on are divided by the factor 40, and the softmax scale carries
    mscale_all_dim's factor squared."""
    a = get_config("deepseek-v2-lite").mla
    inv = attn.yarn_inv_freq(a, 10000.0)
    extra = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    assert np.all((inv[10:23] <= extra[10:23] * (1 + 1e-6))
                  & (inv[10:23] >= extra[10:23] / 40 * (1 - 1e-6)))
    want = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert attn.mla_softmax_scale(a) == pytest.approx(want, rel=1e-12)


def _tiny(**moe):
    cfg = get_config("deepseek-v2-lite", reduced=True)
    if moe:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, **moe))
    return cfg


def _plain_mla(p, cfg, x):
    """MLA as modeling_deepseek.py writes it, in f32: the full (S, S)
    scores, rotate-half rope after de-interleaving the rope dims."""
    a, H = cfg.mla, cfg.num_heads
    B, S, _ = x.shape
    nope, rope, r = a.qk_nope_head_dim, a.qk_rope_head_dim, a.kv_lora_rank
    mm = lambda s, u, v: jnp.einsum(s, u, v, precision=HI)  # noqa: E731
    q = mm("bsd,de->bse", x, p["wq"]).reshape(B, S, H, nope + rope)
    ckv = mm("bsd,de->bse", x, p["wkv_a"])
    c = ckv[..., :r]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + cfg.norm_eps) \
        * p["kv_norm"]
    kv = mm("bsr,re->bse", c, p["wkv_b"]).reshape(B, S, H, nope + a.v_head_dim)
    inv = attn.yarn_inv_freq(a, cfg.rope_theta)
    emb = np.concatenate([np.outer(np.arange(S), inv)] * 2, -1)
    cos, sin = np.cos(emb)[None, :, None], np.sin(emb)[None, :, None]

    def rot(t):
        t = t.reshape(*t.shape[:-1], rope // 2, 2).swapaxes(-1, -2).reshape(
            t.shape)
        half = jnp.concatenate([-t[..., rope // 2:], t[..., :rope // 2]], -1)
        return t * cos + half * sin

    q_pe, k_pe = rot(q[..., nope:]), rot(ckv[..., None, r:])
    s = (mm("bqhe,bkhe->bhqk", q[..., :nope], kv[..., :nope])
         + mm("bqhe,bkxe->bhqk", q_pe, k_pe)) * attn.mla_softmax_scale(a)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), kv[..., nope:])
    return mm("bse,ed->bsd", o.reshape(B, S, -1), p["wo"])


def test_mla_matches_plain_f32():
    cfg = _tiny()
    p = attn.attn_init(jax.random.key(0), cfg, jnp.float32)
    p["kv_norm"] = 1 + 0.1 * jax.random.normal(jax.random.key(1),
                                               p["kv_norm"].shape)
    B, S = 2, 24
    x = jax.random.normal(jax.random.key(2), (B, S, cfg.d_model))
    pos = jnp.arange(S)[None].repeat(B, 0)
    with jax.default_matmul_precision("highest"):
        got, (c, k_pe) = attn.attn_apply(p, cfg, x, pos)
    want = _plain_mla(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=2e-5)
    # the latent a decode cache holds: r + rope numbers a token
    assert c.shape == (B, S, cfg.mla.kv_lora_rank)
    assert k_pe.shape == (B, S, 1, cfg.mla.qk_rope_head_dim)


def _moe_params(cfg, key, scale=1.0):
    p = moe_mod.moe_init(key, cfg, jnp.float32)
    p["router"] = p["router"] * scale
    return p


def _plain_moe(p, cfg, x):
    """Every held expert on every token, weighted by its top-k softmax
    probability (0 where not chosen), plus the shared experts."""
    m = cfg.moe
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(jnp.dot(xf, p["router"], precision=HI), -1)
    kth = jnp.sort(probs, -1)[:, -m.top_k][:, None]
    w = jnp.where(probs >= kth, probs, 0.0)
    w = w[:, m.first_expert:m.first_expert + m.held]
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", xf, p["w_gate"], precision=HI))
    h = h * jnp.einsum("nd,edf->enf", xf, p["w_up"], precision=HI)
    y = jnp.einsum("enf,efd->end", h, p["w_down"], precision=HI)
    out = jnp.einsum("ne,end->nd", w, y, precision=HI)
    if "shared" in p:
        out = out + swiglu_apply(p["shared"], xf)
    return out.reshape(x.shape)


def test_shares_add_up_to_the_uncut_layer():
    """64 routed experts, top-6, cut into 8 shares of 8 experts (share j
    holds experts 8j..8j+7): the shares' outputs, the shared experts
    counted once, add up to the layer that holds all 64."""
    cfg = get_config("deepseek-v2-lite", reduced=True).replace(d_model=32)
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, num_experts=64, top_k=6, d_ff_expert=16))
    full = _moe_params(cfg, jax.random.key(3), scale=20.0)
    x = jax.random.normal(jax.random.key(4), (2, 40, 32))
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_mod.moe_apply(full, cfg, x)
        shared = swiglu_apply(full["shared"], x.reshape(-1, 32)).reshape(
            x.shape)
        parts = []
        for j in range(8):
            cj = cfg.replace(moe=dataclasses.replace(
                cfg.moe, experts_held=8, first_expert=8 * j))
            pj = dict(full, **{k: full[k][8 * j:8 * j + 8]
                               for k in ("w_gate", "w_up", "w_down")})
            out, _ = moe_mod.moe_apply(pj, cj, x)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(_plain_moe(pj, cj, x)),
                                       rtol=1e-5, atol=2e-5)
            parts.append(out - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(_plain_moe(full, cfg, x)),
                               rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("tokens", [8, 3 * moe_mod.TILE + 5])
def test_skewed_router_drops_no_token(tokens):
    """A router that sends every token to the same top-k experts: each of
    them gets all the tokens (several row tiles each at the larger size),
    and every token's output is the plain layer's; nothing is dropped."""
    cfg = _tiny(num_experts=8, top_k=2, experts_held=4, first_expert=0)
    p = _moe_params(cfg, jax.random.key(5))
    # positive inputs and router columns falling with the expert index:
    # experts 0 and 1 win on every token
    p["router"] = jnp.broadcast_to(
        jnp.arange(8, 0, -1, dtype=jnp.float32), p["router"].shape) * 0.05
    x = jnp.abs(jax.random.normal(jax.random.key(6), (1, tokens,
                                                       cfg.d_model)))
    _, idx = moe_mod.route(p, cfg, x.reshape(tokens, -1))
    assert np.all(np.sort(np.asarray(idx), -1) == [0, 1])
    with jax.default_matmul_precision("highest"):
        got, _ = moe_mod.moe_apply(p, cfg, x)
    want = _plain_moe(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=2e-5)
    routed = got - swiglu_apply(p["shared"], x.reshape(tokens, -1)).reshape(
        x.shape)
    assert np.all(np.abs(np.asarray(routed)).max(-1) > 0)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its
    equations (scan, cond and call bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_combine_sums_the_slots_without_an_f32_copy():
    """The top-k combine at the share test's sizes, in bf16 as the cell
    runs it: no f32 value holds every token's K slot rows (N·K·d
    elements), and no matmul takes the (N, K) f32 gates at a precision
    that would round them to bf16."""
    cfg = get_config("deepseek-v2-lite", reduced=True).replace(d_model=32)
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, num_experts=64, top_k=6, d_ff_expert=16, experts_held=8,
        first_expert=8))
    p = moe_mod.moe_init(jax.random.key(3), cfg, jnp.bfloat16)
    x = jax.random.normal(jax.random.key(4), (2, 40, 32), jnp.bfloat16)
    N, K, d = 80, 6, 32
    jaxpr = jax.make_jaxpr(
        lambda p, x: moe_mod.moe_dropless_apply(p, cfg, x))(p, x).jaxpr
    full = [str(v.aval) for e in _eqns(jaxpr) for v in e.outvars
            if v.aval.dtype == jnp.float32 and v.aval.size == N * K * d]
    assert not full, full
    for e in _eqns(jaxpr):
        if e.primitive.name != "dot_general":
            continue
        if any({N, K} <= set(v.aval.shape) for v in e.invars):
            prec = e.params["precision"]
            assert prec is not None and all(
                q == jax.lax.Precision.HIGHEST for q in prec), e


def test_untied_server_holds_no_input_embedding():
    vfl = VFLConfig(num_parties=4)
    ds = TransformerVFLModel(build_model(_tiny()), vfl)
    w0 = jax.eval_shape(ds.init_server, jax.random.key(0))
    assert "embed" not in w0 and "lm_head" in w0 and "dense_layers" in w0
    qwen = TransformerVFLModel(
        build_model(get_config("qwen1.5-0.5b", reduced=True)), vfl)
    assert "embed" in jax.eval_shape(qwen.init_server, jax.random.key(0))


def test_zoo_step_runs_without_the_embedding():
    from repro.launch import steps as step_lib
    vfl = VFLConfig(num_parties=4, party_hidden=16, max_delay=2, mu=1e-3,
                    lr_party=1e-3, lr_server=1e-3)
    _, init, step = step_lib.make_vfl_zoo_step(build_model(_tiny()), vfl)
    state = init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 512)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
    step = jax.jit(step)
    for _ in range(2):
        new, h = step(state, batch)
        assert np.isfinite(float(h))
        moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)),
                             state.w0, new.w0)
        assert all(jax.tree.leaves(moved["lm_head"]))
        state = new
    assert "embed" not in state.w0


def test_server_loss_matches_the_reference():
    from chipbench.common import load_module
    from chipbench.weights import fill
    fam = load_module("families", "deepseek_v2")
    ref = load_module("reference", "deepseek_v2_zoo")
    cfg = json.loads((ROOT / "chipbench/configs/deepseek-v2-lite.ep8.zoo-q4"
                      ".json").read_text())
    cfg["model"].update(fam.TINY, torch_dtype="float32")
    model = build_model(fam.program_config(cfg))
    vm = TransformerVFLModel(model, VFLConfig(num_parties=4))
    # weights large enough that attention, routing and every expert move
    # the loss well away from log(vocab)
    w0 = fill(jax.eval_shape(vm.init_server, jax.random.key(0)),
              jax.random.key(1), 0.3)
    x = jax.random.normal(jax.random.key(2), (2, 16, 64))
    tg = jax.random.randint(jax.random.key(3), (2, 16), 0, 512)
    got = model.loss(w0, {"embeds": x, "targets": tg})[0]
    want = ref.server_loss(w0, x, tg, cfg["model"])
    assert abs(float(want) - math.log(512)) > 0.5
    assert float(got) == pytest.approx(float(want), rel=1e-5)
