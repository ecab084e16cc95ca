"""Config dataclasses for the repro framework.

Every assigned architecture gets a ``ModelConfig`` (full, paper-exact sizes)
plus a ``reduced()`` variant (<=2 layers, d_model<=512, <=4 experts) used by
the CPU smoke tests. The FULL configs are only ever lowered via
``launch/dryrun.py`` (ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01  # load-balance auxiliary loss
    # "capacity": the (E, C, d) dispatch that drops tokens over capacity,
    # renormalizes the top-k gates and adds the Switch balance loss; its
    # buffer shards over the mesh's 'model' axis (launch/dryrun.py's
    # meshes). "dropless": a held share of the routed experts, every
    # routed (token, slot) pair computed, the router in f32 and the top-k
    # probabilities as they are (DeepSeek's MoEGate)
    dispatch: str = "capacity"
    num_shared_experts: int = 0   # dense SwiGLU of width n * d_ff_expert
    experts_held: int = 0         # this chip's share: experts
    first_expert: int = 0         # [first, first + held); 0 held = all

    def __post_init__(self):
        if self.dispatch not in ("capacity", "dropless"):
            raise ValueError(f"unknown MoE dispatch {self.dispatch!r}")
        if (self.experts_held or self.first_expert) and \
                self.dispatch != "dropless":
            raise ValueError("only the dropless layer holds a share")
        if self.dispatch == "dropless" and self.router_aux_coef:
            raise ValueError("the dropless layer has no balance loss")
        if self.first_expert + self.held > self.num_experts:
            raise ValueError("the held share runs past the routed experts")

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2) with YaRN rope."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # YaRN (rope_scaling); factor 1 is plain rope
    rope_factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    kind: str = "rwkv6"          # 'rwkv6' | 'mamba2'
    state_size: int = 16          # N for mamba-style; head_size for rwkv
    expand: int = 2               # d_inner = expand * d_model (mamba)
    chunk_size: int = 128         # chunked-scan block length
    decay_lora_rank: int = 64     # rwkv6 data-dependent decay LoRA rank


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int                # 0 for attention-free archs
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // num_heads
    qkv_bias: bool = False
    qk_norm: bool = False         # chameleon-style stabilization
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    pos_emb: str = "rope"         # rope | sinusoidal | none
    norm_eps: float = 1e-5
    sliding_window: Optional[int] = None   # None = full attention
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None   # latent attention in every layer
    first_k_dense: int = 0        # leading dense layers before the MoE stack
    ssm: Optional[SSMConfig] = None
    # --- enc-dec (whisper) ---
    enc_dec: bool = False
    num_encoder_layers: int = 0
    encoder_frames: int = 1500    # precomputed conv-frontend frames (STUB input)
    # --- modality frontend stub ---
    frontend: str = "none"        # none | audio_stub | vq_stub
    # --- misc ---
    dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    chunked_ce: bool = False      # flash cross-entropy (never materialize
    #                               logits; §Perf C2 / big-vocab training)
    kv_cache_dtype: str = "model"  # "model" (= activation dtype) | "int8"
    #                               (quantized serving cache, per-position/
    #                               head scales — halves decode cache HBM)
    citation: str = ""

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads == 0:
            return 0
        return self.d_model // self.num_heads

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Can this config serve 500k-token contexts?"""
        return (self.family in ("ssm", "hybrid")
                or self.sliding_window is not None)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: <=2 layers, d_model<=512, <=4 experts."""
        d_model = min(self.d_model, 256)
        n_heads = 0 if self.num_heads == 0 else min(self.num_heads, 4)
        ratio = max(1, (self.num_heads or 1) // max(1, self.num_kv_heads or 1))
        kv = 0 if n_heads == 0 else max(1, n_heads // min(ratio, n_heads))
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(self.moe, num_experts=4,
                                      top_k=min(self.moe.top_k, 2),
                                      d_ff_expert=128, experts_held=0,
                                      first_expert=0)
        mla = None
        if self.mla is not None:
            mla = dataclasses.replace(self.mla, kv_lora_rank=32,
                                      qk_nope_head_dim=32,
                                      qk_rope_head_dim=16, v_head_dim=32)
        ssm = None
        if self.ssm is not None:
            ssm = dataclasses.replace(self.ssm, chunk_size=16,
                                      decay_lora_rank=8)
        return dataclasses.replace(
            self, num_layers=2, d_model=d_model, num_heads=n_heads,
            num_kv_heads=kv, head_dim=64 if n_heads else 0,
            d_ff=min(self.d_ff, 512), vocab_size=min(self.vocab_size, 512),
            moe=moe, mla=mla, first_k_dense=min(self.first_k_dense, 1),
            ssm=ssm, num_encoder_layers=min(self.num_encoder_layers, 2),
            encoder_frames=min(self.encoder_frames, 32),
            sliding_window=(min(self.sliding_window, 64)
                            if self.sliding_window else None),
            dtype="float32", remat=False)

    def num_params(self) -> int:
        """Analytic parameter count (for 6ND model-FLOPs and mem napkin math)."""
        d, hd = self.d_model, self.resolved_head_dim
        H, KV, L = self.num_heads, self.num_kv_heads, self.num_layers
        p = self.vocab_size * d                      # embedding
        if not self.tie_embeddings:
            p += d * self.vocab_size                 # lm head
        per_layer = 0
        if self.mla is not None:
            a = self.mla
            per_layer += (d * H * a.qk_head_dim
                          + d * (a.kv_lora_rank + a.qk_rope_head_dim)
                          + a.kv_lora_rank * H * (a.qk_nope_head_dim
                                                  + a.v_head_dim)
                          + H * a.v_head_dim * d)
        elif self.family != "ssm" and H:
            per_layer += d * H * hd + 2 * d * KV * hd + H * hd * d
            if self.qkv_bias:
                per_layer += (H + 2 * KV) * hd
        if self.family == "ssm":
            # rwkv6 time-mix: r,k,v,g,o projections + decay lora + mixes
            per_layer += 5 * d * d + 2 * self.ssm.decay_lora_rank * d
            per_layer += 3 * d * self.d_ff            # channel mix (k, v, r)
        elif self.family == "hybrid":
            di = self.ssm.expand * d
            per_layer += 2 * d * di + di * d + di * self.ssm.state_size * 2
        attn_layer = per_layer
        if self.moe is not None:
            m = self.moe
            per_layer += d * m.num_experts            # router
            per_layer += (m.held + m.num_shared_experts) * 3 * d \
                * m.d_ff_expert
        elif self.family != "ssm":
            per_layer += 3 * d * self.d_ff            # swiglu
        dense = self.first_k_dense
        p += (L - dense) * per_layer + dense * (attn_layer + 3 * d * self.d_ff)
        if self.enc_dec:
            enc_per = d * H * hd * 2 + 2 * d * KV * hd * 0  # rough: same attn
            enc_per = 4 * d * d + 2 * d * self.d_ff
            p += self.num_encoder_layers * enc_per
            p += L * (4 * d * d)                      # cross attention
        return int(p)

    def num_active_params(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.moe is None:
            return self.num_params()
        total = self.num_params()
        L = self.num_layers - self.first_k_dense
        all_expert = (L * self.moe.held * 3
                      * self.d_model * self.moe.d_ff_expert)
        active_expert = (L * self.moe.top_k * 3
                         * self.d_model * self.moe.d_ff_expert)
        return int(total - all_expert + active_expert)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


INPUT_SHAPES = {
    "train_4k":    ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k":   ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class DPConfig:
    """Differential privacy at the codec seam (src/repro/dp, docs/dp.md).

    The defended release is every party->server payload (the c function
    values): each per-sample entry is clipped to ``[-clip, clip]`` and
    perturbed with mechanism noise of scale ``noise_multiplier * clip``
    BEFORE the up-link codec runs — DPZV-style, at the single
    ``ZOExchange.encode_up`` seam every executor shares.

    ``epsilon`` is the per-party (eps, delta)-DP target over a whole run
    (parallel composition across parties: feature blocks are disjoint,
    so each party's guarantee depends only on its OWN releases);
    ``epsilon=inf`` turns the subsystem transparently off (no clip, no
    noise — bit-identical to ``dp=None``). ``noise_multiplier`` is the
    resolved noise scale in clip units; leave it ``None`` and let
    ``repro.dp.accountant.resolve_dp(dp, rounds=...)`` calibrate it from
    the target epsilon once the round budget is known — the exchange
    refuses to run with an uncalibrated target.
    """
    epsilon: Optional[float] = None     # flag: --dp-epsilon — target eps
    #                                     over the run (inf = off)
    delta: float = 1e-5                 # flag: --dp-delta
    clip: Optional[float] = None        # flag: --dp-clip — REQUIRED when
    #                                     enabled: |c_i| <= clip
    mechanism: str = "gaussian"         # internal-only: gaussian (RDP) |
    #                                     laplace (pure-DP); library/bench
    #                                     knob, the CLI defense is gaussian
    noise_multiplier: Optional[float] = None   # internal-only: sigma (noise
    #                                     std = sigma*clip) — resolved by the
    #                                     accountant, never set by hand
    sample_rate: Optional[float] = None  # internal-only: Poisson-subsampling
    #                                      rate q of the minibatch draw;
    #                                      opt-in: None means account WITHOUT
    #                                      amplification (the pre-existing,
    #                                      conservative curve)

    def __post_init__(self):
        if self.mechanism not in ("gaussian", "laplace"):
            raise ValueError(
                f"unknown DP mechanism {self.mechanism!r}; "
                f"have gaussian, laplace")
        if self.sample_rate is not None:
            if not 0.0 < self.sample_rate <= 1.0:
                raise ValueError(
                    f"sample_rate must be in (0, 1], got {self.sample_rate}")
            if self.mechanism != "gaussian":
                raise ValueError(
                    "subsampled amplification is only implemented for the "
                    "gaussian mechanism (MTZ19-style RDP bound); drop "
                    "sample_rate or use mechanism='gaussian'")
        if self.epsilon is not None and self.epsilon <= 0:
            raise ValueError(f"epsilon must be > 0, got {self.epsilon}")
        if not 0.0 < self.delta < 1.0:
            raise ValueError(f"delta must be in (0, 1), got {self.delta}")
        if self.noise_multiplier is not None and self.noise_multiplier < 0:
            raise ValueError("noise_multiplier must be >= 0")
        import math
        if (self.noise_multiplier == 0.0 and self.epsilon is not None
                and math.isfinite(self.epsilon)):
            raise ValueError(
                "noise_multiplier=0 (clip-only) cannot meet a finite "
                "epsilon target — drop the epsilon or supply real noise")
        if self.enabled and self.clip is None:
            raise ValueError(
                "DP epsilon/noise without a clip bound is incoherent: the "
                "mechanism's sensitivity IS the clip — set DPConfig.clip")
        if self.clip is not None and self.clip <= 0:
            raise ValueError(f"clip must be > 0, got {self.clip}")

    @property
    def enabled(self) -> bool:
        """Whether any defense actually applies (eps=inf means OFF)."""
        import math
        if self.noise_multiplier is not None:
            return True
        return self.epsilon is not None and math.isfinite(self.epsilon)

    @property
    def resolved(self) -> bool:
        """Whether the noise scale is known (ready to run)."""
        return not self.enabled or self.noise_multiplier is not None


@dataclass(frozen=True)
class VFLConfig:
    """The paper's framework knobs (Section 3)."""
    num_parties: int = 8          # flag: --parties — q
    party_hidden: int = 128       # internal-only: width of the party tower
    #                               F_m (--arch sizes the models)
    party_layers: int = 2         # internal-only: depth of F_m (paper:
    #                               2-layer FCN; sized by --arch)
    direction: str = "gaussian"   # internal-only: gaussian (AsyREVEL-Gau) |
    #                               uniform (-Uni) | rademacher (the law the
    #                               Pallas zo_update kernel draws);
    #                               library/bench knob
    mu: float = 1e-3              # smoothing parameter mu_m (--mu)
    lr_party: float = 1e-3        # flag: --lr — eta_m
    lr_server: float = 1e-3 / 8   # flag: --lr — eta_0 = eta / q (paper
    #                               setting, derived from the same flag)
    max_delay: int = 4            # internal-only: tau (Assumption 4) for
    #                               the thread executor; the TCP runtime's
    #                               bound is RuntimeConfig.max_staleness
    activation_probs: Optional[Tuple[float, ...]] = None  # internal-only:
    #                               p_m (Assumption 3); bench schedule knob
    num_directions: int = 1       # internal-only: directions averaged per
    #                               estimate (variance reduction,
    #                               beyond-paper; paper cites Liu et al.
    #                               2018); bench/library knob
    lam: float = 1e-4             # internal-only: regularizer weight lambda
    #                               (paper constant)
    perturb_server: bool = True   # internal-only: also ZO-update w_0
    #                               (Eq. 17); losslessness bench toggles it
    codec: str = "f32"            # up-link payload codec for the c values
    #                               (core/exchange.py: f32|bf16|int8; --codec)
    dp: Optional[DPConfig] = None  # flag: --dp-epsilon — clip-then-noise
    #                               defense at the codec seam (src/repro/dp;
    #                               None = undefended)
    fused: bool = False           # route the releases (defend, encode_up,
    #                               roundtrip_up), apply_direction and the
    #                               host executor's release jits through
    #                               the kernels/fused_round fast path
    #                               (bitwise equal to the unfused seam;
    #                               --fused); the ZO draw and its apply
    #                               are core/zoo's either way


@dataclass(frozen=True)
class NetworkConfig:
    """Per-link channel model for the wire subsystem (core/wire.py).

    A message of ``n`` bytes on a link costs
    ``scale * (latency_s + n / bandwidth_Bps + U(0, jitter_s))`` seconds,
    where ``scale`` is the per-party link multiplier (``party_scale[m]``
    for party m's link, 1.0 past the tuple's end — heterogeneous links /
    stragglers). The defaults are the paper's Table-3 channel constants
    (``core/comms.py:paper_ratio``), so the 'lan' profile reproduces the
    paper's reported time ratios from measured message bytes.
    """
    name: str = "lan"
    latency_s: float = 5e-5       # per-message (Table 3's channel model)
    bandwidth_Bps: float = 1e8
    jitter_s: float = 0.0         # uniform [0, jitter_s) extra per message
    party_scale: Optional[Tuple[float, ...]] = None


NETWORK_PROFILES = {
    "lan": NetworkConfig("lan"),
    # trans-continental WAN: 20ms latency, 10 Mbit/s, 2ms jitter
    "wan": NetworkConfig("wan", latency_s=2e-2, bandwidth_Bps=1.25e6,
                         jitter_s=2e-3),
    # LAN where party 0's link is 6x slower (Fig 3's straggler, as a
    # NETWORK property instead of a compute multiplier)
    "straggler": NetworkConfig("straggler", party_scale=(6.0,)),
}


@dataclass(frozen=True)
class RuntimeConfig:
    """Knobs of the multi-process TCP federation runtime (repro/runtime).

    ``schedule`` picks the server's dispatch order: 'serial' processes
    party rounds in strict round-robin (the deterministic reference —
    bit-identical to ``HostAsyncTrainer.run_serial``), 'arrival'
    processes complete rounds in the order they arrive off the sockets
    (AsyREVEL's asynchrony: fast parties never wait for stragglers).

    ``max_staleness`` enforces the paper's tau bound (Assumption 4) on
    the 'arrival' schedule: a round that would race more than tau rounds
    ahead of the slowest party is PARKED until the laggard catches up
    (None = trust the parties, the pre-enforcement behavior).
    """
    host: str = "127.0.0.1"       # internal-only: loopback federation;
    #                               launch/train.py spawns all processes
    port: int = 0                 # internal-only: 0 = OS-assigned
    #                               (reported to parties via the port queue)
    schedule: str = "serial"      # internal-only: serial | arrival dispatch
    #                               order (train.py's --schedule is the LR
    #                               schedule; runtime tests set this direct)
    max_staleness: Optional[int] = None   # internal-only: tau (Assumption
    #                               4); None = off; bench/test knob
    request_timeout_s: float = 15.0   # internal-only: per recv on an open
    #                               connection; fault-injection tests tune it
    max_retries: int = 4          # internal-only: reply waits before a
    #                               party gives up
    connect_retries: int = 60     # internal-only: dial attempts (server
    #                               may start late)
    connect_backoff_s: float = 0.25   # internal-only: dial backoff seconds
    heartbeat_s: float = 2.0      # internal-only: party pings when a reply
    #                               is this late
    ckpt_every: int = 1           # internal-only: checkpoint cadence in
    #                               rounds; --ckpt-dir turns persistence on
    compute_cost_s: float = 0.0   # internal-only: simulated local compute
    #                               per round (speedup bench)
    deadline_s: float = 300.0     # internal-only: hard wall for the whole
    #                               federation, derived from --steps
    trace_dir: Optional[str] = None   # flag: --trace — per-process JSONL
    #                               trace capture dir (repro/obs); None =
    #                               tracing off (the bitwise-default)
    monitor: bool = False         # flag: --monitor — live health plane:
    #                               the parent runs an obs.monitor collector,
    #                               children stream records to it over a
    #                               side socket and obs.health scores them
    #                               online (requires trace_dir; still
    #                               bitwise-invisible to the protocol)


@dataclass(frozen=True)
class ServingConfig:
    """Knobs of the federated inference front end (serving/federated.py).

    One engine step serves every occupied slot with ONE ``serve_down``
    query per party and one batched ``c_up`` answer back — per-message
    latency and codec overhead amortize over ``slots`` concurrent
    requests (benchmarks/bench_serving.py measures the frontier).
    """
    requests: int = 0             # flag: --serve — how many inference
    #                               requests to serve (0 = serving off)
    slots: int = 8                # flag: --serve-batch — concurrent
    #                               request slots = max wire batch B
    cache_entries: int = 2048     # flag: --serve-cache — per-party LRU
    #                               answer-cache capacity, keyed
    #                               (sample id, params version)


@dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 8
    seq_len: int = 128
    steps: int = 100
    lr: float = 3e-4
    optimizer: str = "adam"       # adam | sgd | zo_sgd
    schedule: str = "constant"    # constant | cosine | wsd
    warmup_steps: int = 10
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    seed: int = 0
    log_every: int = 10


@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n
