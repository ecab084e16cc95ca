"""Serving launcher: batched prefill + autoregressive decode.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
      --batch 4 --prompt-len 32 --gen-len 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import steps as step_lib
from repro.models import build_model
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.logging import MetricLogger


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen-len", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    log = MetricLogger(f"serve:{args.arch}")
    key = jax.random.key(args.seed)
    params = model.init(key)
    B, P, G = args.batch, args.prompt_len, args.gen_len

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32)
    frames = None
    if cfg.enc_dec:
        frames = jnp.asarray(rng.normal(
            size=(B, cfg.encoder_frames, cfg.d_model)).astype(np.float32))

    serve_step = jax.jit(step_lib.make_serve_step(model))
    cache = model.init_cache(params, B, max_len=P + G, frames=frames)

    # prefill by replaying the prompt through decode (KV-correct for every
    # family, incl. SSM state builds); batched serving path
    t0 = time.perf_counter()
    logits = None
    for pos in range(P):
        logits, cache = serve_step(params, cache, prompts[:, pos:pos + 1],
                                   jnp.int32(pos))
    prefill_t = time.perf_counter() - t0

    toks = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t0 = time.perf_counter()
    for g in range(G):
        toks.append(tok)
        logits, cache = serve_step(params, cache, tok, jnp.int32(P + g))
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits[:, 0] / args.temperature)[:, None].astype(
                jnp.int32)
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    decode_t = time.perf_counter() - t0
    out = jnp.concatenate(toks, axis=1)
    log.log(0, prefill_s=prefill_t, decode_s=decode_t,
            tok_per_s=B * G / max(decode_t, 1e-9))
    print("generated token ids (first row):", np.asarray(out[0]))
    return np.asarray(out)


if __name__ == "__main__":
    main()
