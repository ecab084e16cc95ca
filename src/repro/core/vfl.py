"""The VFL composite model — problem (P), Section 3.1.

    f_i(w_0, w) = F_0(w_0, c_{i,1}, ..., c_{i,q}; y_i) + lam * sum_m g(w_m),
    c_{i,m} = F_m(w_m; x_{i,m})

Each party m privately holds a vertical feature slice x_{i,m} and a black-box
local model F_m; the server holds labels and the black-box global model F_0.
Only the c values (party -> server) and scalar losses (server -> party) ever
cross the boundary.

Three concrete instances:
  * PaperLRModel  — generalized linear model, Eq. (22): F_m = w_m^T x_m
    (scalar c), F_0 = log(1+exp(-y * sum_m c_m)), nonconvex regularizer
    g(w) = sum_j w_j^2/(1+w_j^2).
  * PaperFCNModel — the paper's deep model: party towers are 2-layer FCNs
    (d_m x 128, 128 x 1, ReLU) with scalar output, server is a (q x 10) FC +
    softmax CE.
  * TransformerVFLModel — framework-scale instance: parties own disjoint
    slices of the embedding feature space (each party embeds the shared token
    ids through its PRIVATE d_model/q-column embedding slice + a small MLP
    tower); the server model F_0 is any assigned architecture from
    repro/models consuming the concatenated party embeddings.

All parties share a tower STRUCTURE (so party params stack over a leading q
axis for vmap) but their values are private and independently initialized.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, VFLConfig
from repro.configs.paper_models import PaperFCNConfig, PaperLRConfig
from repro.models.layers import cross_entropy_loss, dense_init


def split_features(d_total: int, q: int) -> list[tuple[int, int]]:
    """Vertical partition: q nearly-equal contiguous feature blocks
    (paper: 'vertically partition the data into q non-overlapped parts with
    nearly equal number of features')."""
    base, rem = divmod(d_total, q)
    out, start = [], 0
    for m in range(q):
        size = base + (1 if m < rem else 0)
        out.append((start, size))
        start += size
    return out


def pad_features(x, d_total: int, q: int):
    """Pad feature rows to q * ceil(d/q) so every party block has the same
    width (lets the party index be a traced value inside lax.scan)."""
    pad = -(-d_total // q)
    target = pad * q
    if x.shape[-1] == target:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, target - x.shape[-1])])


def nonconvex_reg(tree) -> jnp.ndarray:
    """g(w) = sum_j w_j^2 / (1 + w_j^2)  (Eq. 22's regularizer)."""
    leaves = jax.tree.leaves(tree)
    tot = jnp.zeros((), jnp.float32)
    for x in leaves:
        x32 = x.astype(jnp.float32)
        tot = tot + jnp.sum(jnp.square(x32) / (1.0 + jnp.square(x32)))
    return tot


class VFLModel:
    """Interface. c values are (B, c_dim) per party.

    Instances hash by (type, config) so jit caches with the model as a
    static argument survive re-instantiation (same semantics => same
    compiled executable).
    """

    num_parties: int

    def _hash_key(self):
        return (type(self).__name__, getattr(self, "cfg", None))

    def __hash__(self):
        return hash(self._hash_key())

    def __eq__(self, other):
        return (type(self) is type(other)
                and self._hash_key() == other._hash_key())

    def init_party(self, key, m: int):
        raise NotImplementedError

    def init_server(self, key):
        raise NotImplementedError

    def party_forward(self, w_m, x_m, m: int):
        """F_m: private features -> c_m."""
        raise NotImplementedError

    def server_forward(self, w0, cs, y):
        """F_0: list/stack of c_m + labels -> scalar loss (no reg)."""
        raise NotImplementedError

    def server_predict(self, w0, cs):
        """F_0's decision from a received c table (B, q) — no labels, no
        party data: the inference-serving reduce (serving/federated.py).
        ``predict`` composes party forwards with this."""
        raise NotImplementedError

    def regularizer(self, w_m):
        return jnp.zeros((), jnp.float32)

    def slice_features(self, x, m):
        """Extract party m's private vertical slice from the (padded) row.
        `m` may be a traced index."""
        raise NotImplementedError

    def replace_party_output(self, cs, c_new, m):
        """Swap party m's column in the stacked c tensor (B, q, ...)."""
        return cs.at[:, m].set(c_new.astype(cs.dtype))

    def map_party_outputs(self, cs, fn):
        """Apply fn(c_m, m) to each party's block of the stacked c tensor
        independently — the per-MESSAGE granularity of the wire protocol
        (each party uploads its own c vector; a codec must see one
        message at a time, not the joint table)."""
        return jnp.stack([fn(cs[:, m], m)
                          for m in range(self.num_parties)], axis=1)

    # batch adapters (overridden by TransformerVFLModel)
    def party_args(self, batch):
        return batch["x"]

    def server_args(self, batch):
        return batch["y"]

    # --- conveniences -----------------------------------------------------
    def init_parties_stacked(self, key):
        keys = jax.random.split(key, self.num_parties)
        per = [self.init_party(keys[m], m) for m in range(self.num_parties)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    def all_party_outputs(self, stacked_w, x):
        """c_m for every party; party towers share structure -> vmap."""
        def one(m, w_m):
            return self.party_forward(w_m, self.slice_features(x, m), m)
        return jnp.stack([one(m, jax.tree.map(lambda a: a[m], stacked_w))
                          for m in range(self.num_parties)], axis=1)

    def stale_party_outputs(self, hist, slots, x):
        """c_m for every party from its DELAYED params: party m reads the
        ring buffer's slot slots[m]. hist leaves: (tau+1, q, ...); slots:
        (q,) int. Gathers each party's whole stale params; a model whose
        party leaf is a lookup table read only at the batch's rows
        overrides this to read just those rows."""
        q = slots.shape[0]
        with jax.named_scope("ring_buffer"):
            stale = jax.tree.map(lambda h: h[slots, jnp.arange(q)], hist)
        with jax.named_scope("party_forward"):
            return self.all_party_outputs(stale, x)

    def full_loss(self, w0, stacked_w, x, y, lam: float):
        """Centralized view of problem (P) — used by NonF baseline & tests."""
        cs = self.all_party_outputs(stacked_w, x)
        reg = sum(self.regularizer(jax.tree.map(lambda a: a[m], stacked_w))
                  for m in range(self.num_parties))
        return self.server_forward(w0, cs, y) + lam * reg


# ------------------------------------------------------------------ LR -----

class PaperLRModel(VFLModel):
    """Black-box federated nonconvex logistic regression (Eq. 22)."""

    def __init__(self, cfg: PaperLRConfig):
        self.cfg = cfg
        self.num_parties = cfg.num_parties
        self.pad = -(-cfg.num_features // cfg.num_parties)

    def init_party(self, key, m: int):
        return {"w": jnp.zeros((self.pad,), jnp.float32)}

    def init_server(self, key):
        return {"b": jnp.zeros((), jnp.float32)}

    def slice_features(self, x, m):
        # x must be padded to q*pad (core.vfl.pad_features); m may be traced
        return jax.lax.dynamic_slice_in_dim(x, m * self.pad, self.pad,
                                            axis=-1)

    def party_forward(self, w_m, x_m, m: int):
        return x_m @ w_m["w"]             # (B,)

    def server_forward(self, w0, cs, y):
        z = jnp.sum(cs, axis=1) + w0["b"]
        return jnp.mean(jnp.log1p(jnp.exp(-y * z)))

    def regularizer(self, w_m):
        return nonconvex_reg(w_m)

    def server_predict(self, w0, cs):
        return jnp.sign(jnp.sum(cs, axis=1) + w0["b"])

    def predict(self, w0, stacked_w, x):
        return self.server_predict(w0, self.all_party_outputs(stacked_w, x))


# ----------------------------------------------------------------- FCN -----

class PaperFCNModel(VFLModel):
    """Black-box federated neural network (Section 5.1)."""

    def __init__(self, cfg: PaperFCNConfig):
        self.cfg = cfg
        self.num_parties = cfg.num_parties
        self.pad = -(-cfg.num_features // cfg.num_parties)

    def init_party(self, key, m: int):
        k1, k2 = jax.random.split(key)
        return {"w1": dense_init(k1, self.pad, self.cfg.party_hidden),
                "b1": jnp.zeros((self.cfg.party_hidden,)),
                "w2": dense_init(k2, self.cfg.party_hidden, 1),
                "b2": jnp.zeros((1,))}

    def init_server(self, key):
        return {"w": dense_init(key, self.num_parties, self.cfg.num_classes),
                "b": jnp.zeros((self.cfg.num_classes,))}

    def slice_features(self, x, m):
        return jax.lax.dynamic_slice_in_dim(x, m * self.pad, self.pad,
                                            axis=-1)

    def party_forward(self, w_m, x_m, m: int):
        h = jax.nn.relu(x_m @ w_m["w1"] + w_m["b1"])
        return (h @ w_m["w2"] + w_m["b2"])[..., 0]     # (B,)

    def server_forward(self, w0, cs, y):
        logits = cs @ w0["w"] + w0["b"]                # (B, classes)
        return cross_entropy_loss(logits, y)

    def server_predict(self, w0, cs):
        return jnp.argmax(cs @ w0["w"] + w0["b"], axis=-1)

    def predict(self, w0, stacked_w, x):
        return self.server_predict(w0, self.all_party_outputs(stacked_w, x))


# --------------------------------------------------------- Transformer -----

class TransformerVFLModel(VFLModel):
    """Framework-scale VFL: assigned architecture as the server model F_0.

    Party m privately owns columns [m*dq : (m+1)*dq) of the embedding
    feature space (dq = d_model/q) — its 'vertical feature slice' — plus a
    small MLP tower. c_m = tower_m(embed_m[tokens]) with shape (B,S,dq);
    the server concatenates to (B,S,d_model) and runs the backbone.
    """

    def __init__(self, model: Any, vfl: VFLConfig):
        from repro.models.model import Model
        self.model: Model = model
        self.vfl = vfl
        self.num_parties = vfl.num_parties
        cfg: ModelConfig = model.cfg
        assert cfg.d_model % vfl.num_parties == 0, \
            "d_model must divide by q for the vertical embedding split"
        self.dq = cfg.d_model // vfl.num_parties

    def _hash_key(self):
        return (type(self).__name__, self.model.cfg, self.vfl)

    def init_party(self, key, m: int):
        cfg = self.model.cfg
        k0, k1, k2 = jax.random.split(key, 3)
        h = self.vfl.party_hidden
        return {
            "embed": (jax.random.normal(
                k0, (cfg.vocab_size, self.dq), jnp.float32) * 0.02),
            "w1": dense_init(k1, self.dq, h),
            "w2": dense_init(k2, h, self.dq),
        }

    def init_server(self, key):
        """The server's backbone. Untied, it holds no input embedding: the
        party towers give its inputs, so the ZO step would only draw,
        perturb and update a table that nothing reads."""
        w0 = self.model.init(key)
        if not self.model.cfg.tie_embeddings:
            del w0["embed"]
        return w0

    def slice_features(self, x, m: int):
        return x        # tokens are shared ids; the SLICE is the embedding

    @staticmethod
    def _tower(w_m, e):
        h = jax.nn.gelu(e @ w_m["w1"])
        return e + h @ w_m["w2"]                        # residual tower

    def party_forward(self, w_m, tokens, m: int):
        return self._tower(w_m, w_m["embed"][tokens])   # e: (B,S,dq)

    def all_party_outputs(self, stacked_w, tokens):
        def one(w_m):
            return self.party_forward(w_m, tokens, 0)
        cs = jax.vmap(one)(stacked_w)                   # (q,B,S,dq)
        return jnp.moveaxis(cs, 0, -2)                  # (B,S,q,dq)

    def stale_party_outputs(self, hist, slots, tokens):
        """The stale c's reading only the embedding rows the batch uses,
        straight out of the ring buffer: gathering whole stale tables
        first would move every party's vocab x dq slice each round. The
        buffer flattens to one (slot, party, token)-row table, as the
        server's own embedding lookup reads its table; the small tower
        leaves are gathered whole."""
        emb = hist["embed"]                             # (tau+1,q,V,dq)
        n, q, vocab, dq = emb.shape
        with jax.named_scope("ring_buffer"):
            base = (slots * q + jnp.arange(q)) * vocab  # (q,)
            e = emb.reshape(n * q * vocab, dq)[
                base[:, None, None] + tokens[None]]     # (q,B,S,dq)
            towers = {k: h[slots, jnp.arange(q)]
                      for k, h in hist.items() if k != "embed"}
        with jax.named_scope("party_forward"):
            cs = jax.vmap(self._tower)(towers, e)       # (q,B,S,dq)
        return jnp.moveaxis(cs, 0, -2)                  # (B,S,q,dq)

    def replace_party_output(self, cs, c_new, m):
        return cs.at[:, :, m].set(c_new.astype(cs.dtype))   # (B,S,q,dq)

    def map_party_outputs(self, cs, fn):
        return jnp.stack([fn(cs[:, :, m], m)                # (B,S,dq) each
                          for m in range(self.num_parties)], axis=2)

    def party_args(self, batch):
        return batch["tokens"]

    def server_args(self, batch):
        return batch

    def server_forward(self, w0, cs, batch):
        B, S = cs.shape[:2]
        embeds = cs.reshape(B, S, -1)                   # concat party slices
        b = dict(batch)
        b["embeds"] = embeds
        loss, _ = self.model.loss(w0, b)
        return loss
