"""Unified Model API over all assigned architecture families.

    model = build_model(get_config("yi-34b"))
    params = model.init(key)
    loss, metrics = model.loss(params, batch)          # train
    logits, aux = model.forward(params, batch)          # prefill
    cache = model.init_cache(params, batch_size, max_len[, frames])
    logits, cache = model.decode_step(params, cache, token, pos)  # serve

Batch dicts:
  LM families   : {"tokens": (B,S) i32, "targets": (B,S) i32}
  vlm (chameleon early-fusion): + {"modality_mask": (B,S) i32}  (VQ stub —
                  image patches are already token ids in the shared vocab)
  audio (whisper): + {"frames": (B,F,d_model)}  (conv frontend STUB output)

For the VFL-ZOO mode (core/vfl.py), ``forward`` also accepts precomputed
input embeddings via batch["embeds"] — the party towers' concatenated output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import transformer as tf
from repro.models.layers import (chunked_cross_entropy, cross_entropy_loss,
                                 embedding_init, rms_norm,
                                 sinusoidal_position_at,
                                 sinusoidal_positions)
from repro.sharding.ctx import constrain


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------- init ---
    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 5)
        params = {
            "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model,
                                    self.dtype),
            "layers": tf.stacked_layers_init(
                ks[1], cfg, self.dtype, cfg.num_layers - cfg.first_k_dense),
            "final_norm": jnp.ones((cfg.d_model,), self.dtype),
        }
        if cfg.first_k_dense:
            params["dense_layers"] = tf.stacked_layers_init(
                jax.random.fold_in(ks[1], 1), tf.dense_config(cfg),
                self.dtype, cfg.first_k_dense)
        if not cfg.tie_embeddings:
            params["lm_head"] = embedding_init(
                ks[2], cfg.vocab_size, cfg.d_model, self.dtype).T
        if cfg.frontend == "vq_stub":
            params["modality_embed"] = (
                jax.random.normal(ks[3], (2, cfg.d_model), jnp.float32)
                * 0.02).astype(self.dtype)
        if cfg.enc_dec:
            enc_cfg = cfg.replace(enc_dec=False, sliding_window=None)
            params["encoder"] = {
                "layers": tf.stacked_layers_init(ks[4], enc_cfg, self.dtype,
                                                 cfg.num_encoder_layers),
                "final_norm": jnp.ones((cfg.d_model,), self.dtype),
            }
        return params

    # ---------------------------------------------------------- helpers ---
    def _embed(self, params, batch):
        cfg = self.cfg
        if "embeds" in batch:                 # VFL party-tower path
            x = batch["embeds"].astype(self.dtype)
        else:
            x = params["embed"][batch["tokens"]]
        if cfg.frontend == "vq_stub" and "modality_mask" in batch:
            x = x + params["modality_embed"][batch["modality_mask"]]
        if cfg.pos_emb == "sinusoidal":
            S = x.shape[1]
            pos0 = batch.get("pos_offset", 0)
            pe = sinusoidal_positions(S, cfg.d_model) if isinstance(pos0, int) \
                else None
            if pe is not None:
                x = x + pe[None].astype(self.dtype)
        # activations are batch-sharded; never let table shardings leak in
        return constrain(x, ("batch", None, None))

    def _encode(self, params, frames):
        """Whisper encoder over precomputed (stub) frame embeddings."""
        cfg = self.cfg
        enc_cfg = cfg.replace(enc_dec=False, sliding_window=None)
        B, F, _ = frames.shape
        x = frames.astype(self.dtype)
        x = x + sinusoidal_positions(F, cfg.d_model)[None].astype(self.dtype)
        positions = jnp.arange(F)[None, :].repeat(B, 0)
        x, _ = tf.stack_forward(params["encoder"]["layers"], enc_cfg, x,
                                positions, causal=False)
        return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)

    def _backbone(self, params, x, positions, enc_out=None):
        """The leading dense layers, then the scanned stack."""
        cfg = self.cfg
        if cfg.first_k_dense:
            x = tf.prefix_forward(params["dense_layers"], cfg, x, positions)
        return tf.stack_forward(params["layers"], cfg, x, positions,
                                enc_out=enc_out, causal=True)

    def _head(self, params, x):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.dot(x, w.astype(self.dtype))
        return constrain(logits, ("batch", None, "model"))

    # ---------------------------------------------------------- forward ---
    def forward(self, params, batch):
        cfg = self.cfg
        x = self._embed(params, batch)
        B, S = x.shape[:2]
        positions = batch.get("positions",
                              jnp.arange(S)[None, :].repeat(B, 0))
        enc_out = None
        if cfg.enc_dec:
            enc_out = self._encode(params, batch["frames"])
        x, aux = self._backbone(params, x, positions, enc_out)
        return self._head(params, x), aux

    def loss(self, params, batch):
        cfg = self.cfg
        if cfg.chunked_ce:
            # flash CE: backbone to final hidden, then vocab-chunked
            # logsumexp — the (B,S,V) logits tensor never exists
            x = self._embed(params, batch)
            B, S = x.shape[:2]
            positions = batch.get("positions",
                                  jnp.arange(S)[None, :].repeat(B, 0))
            enc_out = (self._encode(params, batch["frames"])
                       if cfg.enc_dec else None)
            x, aux = self._backbone(params, x, positions, enc_out)
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            w = params["embed"].T if cfg.tie_embeddings \
                else params["lm_head"]
            ce = chunked_cross_entropy(x, w.astype(self.dtype),
                                       batch["targets"],
                                       batch.get("loss_mask"))
            return ce + aux, {"ce": ce, "aux": aux}
        logits, aux = self.forward(params, batch)
        ce = cross_entropy_loss(logits, batch["targets"],
                                batch.get("loss_mask"))
        return ce + aux, {"ce": ce, "aux": aux}

    # ----------------------------------------------------------- decode ---
    def init_cache(self, params, batch_size: int, max_len: int, frames=None):
        cfg = self.cfg
        cache = {"layers": tf.stacked_cache_init(
            cfg, batch_size, max_len, self.dtype,
            cfg.num_layers - cfg.first_k_dense)}
        if cfg.first_k_dense:
            cache["dense_layers"] = tf.stacked_cache_init(
                tf.dense_config(cfg), batch_size, max_len, self.dtype,
                cfg.first_k_dense)
        if cfg.enc_dec:
            assert frames is not None, "enc-dec decode needs encoder frames"
            enc_out = self._encode(params, frames)
            # per-layer cross K/V, stacked on the layer axis
            cross = jax.vmap(
                lambda p_l: attn.encode_kv(p_l["cross"], cfg, enc_out)
            )(params["layers"])
            cache["cross_kv"] = cross
        return cache

    def decode_step(self, params, cache, token, pos):
        """token: (B,1) i32 (or {"embeds": (B,1,d)} dict); pos: scalar i32."""
        cfg = self.cfg
        if isinstance(token, dict):
            x = token["embeds"].astype(self.dtype)
        else:
            x = params["embed"][token]
            if cfg.frontend == "vq_stub":
                # modality of the new token defaults to text (mask=0)
                x = x + params["modality_embed"][0][None, None, :]
        if cfg.pos_emb == "sinusoidal":
            pos_b = jnp.broadcast_to(jnp.asarray(pos), (x.shape[0],))
            pe = jax.vmap(lambda q: sinusoidal_position_at(
                q, cfg.d_model))(pos_b)
            x = x + pe[:, None, :].astype(self.dtype)
        new_cache = dict(cache)
        if cfg.first_k_dense:
            x, new_cache["dense_layers"] = tf.stack_decode(
                params["dense_layers"], tf.dense_config(cfg), x,
                cache["dense_layers"], pos)
        x, new_cache["layers"] = tf.stack_decode(
            params["layers"], cfg, x, cache["layers"], pos,
            cross_kv=cache.get("cross_kv"))
        return self._head(params, x), new_cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
