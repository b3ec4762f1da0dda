"""Configuration system for the DSDE reproduction framework.

Every architecture in ``repro/configs/`` builds a :class:`ModelConfig`;
the serving / training / distribution layers consume the sibling configs.

Design notes
------------
* Plain frozen dataclasses — hashable, usable as jit static args.
* ``ModelConfig.reduced()`` derives the CPU smoke-test variant mandated by
  the assignment (<=2 layers, d_model<=512, <=4 experts).
* ``attention_window`` enables the sliding-window variant that makes
  ``long_500k`` tractable for dense architectures (beyond-paper extension,
  see DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Model architecture
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""
    num_experts: int
    top_k: int
    # d_ff of each expert (may differ from the dense d_ff).
    expert_d_ff: int
    # Router options.
    router_jitter: float = 0.0
    load_balance_weight: float = 0.01
    # Sharding strategy: "tp" (tensor-parallel experts, baseline) or
    # "ep" (expert-parallel all-to-all, hillclimb variant).
    sharding: str = "tp"


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) block configuration."""
    state_size: int = 128
    head_dim: int = 64
    expand: int = 2
    chunk_size: int = 256
    conv_width: int = 4
    # number of SSD heads = expand*d_model // head_dim (derived)


@dataclass(frozen=True)
class RGLRUConfig:
    """RecurrentGemma RG-LRU block configuration."""
    lru_width: int = 2560
    conv_width: int = 4
    # pattern: how many recurrent blocks per attention block (2 means
    # [rec, rec, attn] repeating — the paper's 1:2 ratio).
    blocks_per_attention: int = 2
    local_attention_window: int = 2048


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description. One instance per assigned architecture."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // num_heads
    # --- attention options ----------------------------------------------
    qk_norm: bool = False            # qwen3
    qkv_bias: bool = False           # qwen2.5 / qwen2-vl
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None   # qwen2-vl M-RoPE
    attention_window: Optional[int] = None   # sliding-window (mixtral SWA,
                                             # dense long-ctx variant)
    # layout optimization (exact, §Perf): physical KV heads in cache/compute
    # replicated up to this count so the kv dim divides the model axis
    kv_head_pad: Optional[int] = None
    # layout optimization (exact, §Perf): query heads padded (extra heads'
    # wo rows zero) so the head dim divides the model axis
    q_head_pad: Optional[int] = None
    # --- block composition ------------------------------------------------
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    # --- enc-dec (audio) ---------------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    # --- embeddings / head --------------------------------------------------
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    # modality frontend stub: if set, inputs are precomputed embeddings of
    # shape [batch, seq, frontend_dim] instead of token ids.
    frontend_dim: Optional[int] = None
    # citation for provenance (hf model card or arXiv id)
    source: str = ""

    # ----------------------------------------------------------------- utils
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """True if long_500k decode is natively tractable."""
        return (
            self.family in ("ssm", "hybrid")
            or self.attention_window is not None
        )

    def padded_vocab(self, multiple: int = 2048) -> int:
        """Vocab padded so (a) the embedding shards evenly over 16 model
        shards of 128-lane registers (16*128 = 2048) and (b) there is at
        least one spare row serving as the reserved padding token id
        (paper §3.2) — ``pad_id == vocab_size`` always embeds validly."""
        return ((self.vocab_size + multiple) // multiple) * multiple

    def reduced(self) -> "ModelConfig":
        """CPU smoke-test variant of the same family (assignment carve-out:
        <=2 layers, d_model<=512, <=4 experts)."""
        d_model = min(self.d_model, 256)
        num_heads = min(self.num_heads, 4)
        head_dim = max(d_model // num_heads, 16)
        num_kv = max(1, min(self.num_kv_heads, num_heads,
                            max(1, num_heads * self.num_kv_heads // self.num_heads)))
        kw = dict(
            name=self.name + "-reduced",
            num_layers=min(self.num_layers, 2),
            d_model=d_model,
            num_heads=num_heads,
            num_kv_heads=num_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
        )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2),
                expert_d_ff=min(self.moe.expert_d_ff, 256),
            )
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(
                self.ssm, state_size=min(self.ssm.state_size, 32),
                head_dim=32, chunk_size=32)
        if self.rglru is not None:
            kw["rglru"] = dataclasses.replace(
                self.rglru, lru_width=d_model, local_attention_window=64)
        if self.is_encoder_decoder:
            kw["num_encoder_layers"] = min(self.num_encoder_layers, 2)
        if self.frontend_dim is not None:
            kw["frontend_dim"] = d_model
        if self.attention_window is not None:
            kw["attention_window"] = min(self.attention_window, 64)
        if self.mrope_sections is not None:
            # keep 3 sections summing to head_dim//2
            h = head_dim // 2
            kw["mrope_sections"] = (h - 2 * (h // 3), h // 3, h // 3)
        return dataclasses.replace(self, **kw)

    def draft(self) -> "ModelConfig":
        """Same-family draft-model config (the paper's small-draft paradigm):
        ~1/4 depth & width of the target, same vocab + tokenizer."""
        d_model = max(128, self.d_model // 4)
        num_heads = max(2, self.num_heads // 4)
        kw = dict(
            name=self.name + "-draft",
            num_layers=max(2, self.num_layers // 4),
            d_model=d_model,
            num_heads=num_heads,
            num_kv_heads=max(1, min(self.num_kv_heads, num_heads)),
            head_dim=max(32, d_model // num_heads),
            d_ff=max(256, self.d_ff // 4) if self.d_ff else 0,
        )
        if self.moe is not None:
            # drafts are dense — standard practice (cheap, stateless router-free)
            kw["moe"] = None
            kw["family"] = "dense"
            kw["d_ff"] = max(256, self.moe.expert_d_ff)
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(self.ssm, head_dim=32)
        if self.rglru is not None:
            kw["rglru"] = dataclasses.replace(self.rglru, lru_width=d_model)
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# DSDE / speculative decoding
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SpecDecodeConfig:
    """DSDE adapter configuration — defaults follow the paper exactly.

    ``policy`` names a registered :class:`repro.core.policies.SpecPolicy`
    ("dsde" | "static" | "adaedl" | "autoregressive" | "goodput" | any
    policy registered via ``repro.core.policies.register``).

    ``drafter`` names a registered :class:`repro.core.drafters.Drafter`
    ("model" | "ngram" | "self" | any drafter registered via
    ``repro.core.drafters.register_drafter``) — the proposer half of a
    speculation round (DESIGN.md §9), orthogonal to the SL policy."""
    policy: str = "dsde"
    drafter: str = "model"
    sl_min: int = 2                    # paper §3.1.2
    sl_max: int = 10                   # bucket upper bound; Eq.(1) calibrates
    static_sl: int = 4                 # for the static baseline
    # Eq. (5): exponential decay for weighted variance.
    decay: float = 0.85
    short_window: int = 10             # N for Var_w(KLD_short)
    long_window: int = 30              # N for Var_w(KLD_long)
    sf_scale: float = 2.0              # Eq. (3): SF = exp(sf_scale*mu)-1
    # Beyond-paper: scale-invariant SF = exp(sf_scale*(mu/mu_calib - 1))-1
    # (clamped at 0).  Eq. (3)'s absolute constant is tuned to real-LLM KLD
    # magnitudes (~0.1-0.5 nats); miniature/CPU pairs sit at 1-3 nats where
    # the raw form saturates the penalty.  Default off = paper-faithful.
    sf_normalize: bool = False
    # Eq. (1) calibration.
    calibration_steps: int = 4
    calibration_sl: int = 5
    eps: float = 1e-6
    # SL_cap (Eq. 11) on/off — Fig. 9 ablation.
    use_sl_cap: bool = True
    # AdaEDL baseline: stop drafting when entropy-based acceptance lower
    # bound drops below threshold; `adaedl_base` is the paper's base=7.
    adaedl_base: int = 7
    adaedl_threshold: float = 0.1
    # Goodput controller (TurboSpec-style acceptance-EMA policy):
    # EMA decay of the per-round acceptance fraction, the per-draft-step
    # cost relative to one verification (in latency units), and the
    # optimistic acceptance prior used before any observation.
    # ``goodput_draft_cost=None`` (the default) sources the cost from the
    # serving drafter's own ``Drafter.step_cost()`` (model drafters:
    # draft/target FLOP ratio; lookup drafters: ~0); a float here is an
    # explicit override.  Contexts with no drafter in scope (direct
    # policy unit use) fall back to the historical 0.08.
    goodput_ema: float = 0.75
    goodput_draft_cost: Optional[float] = None
    goodput_init_acc: float = 0.7
    # --- drafter knobs (DESIGN.md §9) ----------------------------------
    # ngram: prompt-lookup suffix-match length (the "n" of the n-gram)
    ngram_n: int = 3
    # self: how many leading target layers the early-exit self-draft runs
    self_draft_layers: int = 1
    # sampling
    temperature: float = 0.0           # 0.0 = greedy
    # penalty floor condition (Eq. 8): if SF*WVIR >= penalty_cutoff, SL=SL_min
    penalty_cutoff: float = 1.0


@dataclass(frozen=True)
class ServingConfig:
    max_batch_size: int = 64
    max_seq_len: int = 4096
    max_new_tokens: int = 256
    # reserved padding token id (paper §3.2) — defaults to vocab_size.
    pad_token_id: Optional[int] = None
    eos_token_id: int = 1
    # continuous batching: admit new requests when slots free up.
    continuous_batching: bool = True
    # plan -> dispatch -> collect pipeline (DESIGN.md §7): enqueue round
    # N+1 while round N's outputs are still on the wire and reconcile
    # the host one round behind.  Relies on device-side termination in
    # the round, so greedy token streams are byte-identical to the
    # synchronous engine; False keeps the lockstep step() loop.
    pipelined: bool = False
    # --- paged KV cache (DESIGN.md §4) ---------------------------------
    # block-pool KV layout: sequences hold block tables into a shared
    # pool instead of one dense max_seq_len row per slot; admission is
    # by free-block budget and the scheduler preempts (evict + requeue,
    # recompute on readmit) instead of rejecting when the pool runs dry.
    paged_kv: bool = False
    kv_block_size: int = 16
    # pool size in blocks; None = dense-equivalent capacity
    # (max_batch_size rows of max_seq_len).  Size below that to pack
    # more sequences per byte of HBM than dense rows ever could.
    num_kv_blocks: Optional[int] = None
    # prefix caching (DESIGN.md §12): content-hash committed full blocks
    # and share them copy-on-write across sequences with a common prompt
    # prefix; admission charges only the uncovered suffix and prefill
    # skips the covered tokens.  Requires paged_kv and a non-recurrent
    # model family (per-slot lru/conv state cannot be recovered from the
    # block pool); the engine gates on both.
    prefix_caching: bool = False
    # quantized KV storage (DESIGN.md §4, §13): "none" keeps the pool in
    # the compute dtype; "int8" stores K/V as int8 with per-slot-per-KV-
    # head fp32 amax scales, quantized on write and dequantized inside
    # the verify kv-sweep.  Requires paged_kv and a non-recurrent family
    # (the recurrent rows stay fp and the hybrid cache threading is out
    # of scope); the engine validates.  ``num_kv_blocks`` stays a
    # physical block count — blocks just cost fewer bytes, so an
    # equal-byte budget buys >= 2x blocks (``equal_byte_blocks``).
    kv_quant: str = "none"
    # SLO-aware admission (DESIGN.md §15): how many times a fresh
    # deadline-carrying request whose predicted completion already
    # breaches its deadline may be deferred behind later feasible
    # arrivals before it admits unconditionally anyway.  Bounds the
    # aging so predicted violators are surfaced and de-prioritized but
    # never starved or dropped; 0 disables deferral entirely (predicted
    # violations are still surfaced).
    slo_defer_limit: int = 4
    # precision of the engine's f32 matmuls (prefill and round programs;
    # ``jax.default_matmul_precision``).  At a TPU's default precision an
    # f32 dot is one bf16 pass, and a one-row decode and a K+1-row verify
    # then differ by a sizeable share of the logit spread: greedy
    # speculative streams left the autoregressive one well away from
    # ties on a v5e (PERF.md).  "highest" keeps them equal.
    matmul_precision: str = "highest"

    def blocks_per_seq(self) -> int:
        """Block-table width: worst-case blocks one sequence can hold."""
        return -(-self.max_seq_len // self.kv_block_size)

    def pool_blocks(self) -> int:
        """Resolved pool size in blocks."""
        if self.num_kv_blocks is not None:
            return self.num_kv_blocks
        return self.max_batch_size * self.blocks_per_seq()


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimizerConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0


@dataclass(frozen=True)
class TrainConfig:
    global_batch_size: int = 256
    seq_len: int = 4096
    microbatch_size: Optional[int] = None   # for gradient accumulation
    remat: bool = True                       # activation checkpointing
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    checkpoint_every: int = 500
    checkpoint_dir: str = "/tmp/repro_ckpt"


# ---------------------------------------------------------------------------
# Distribution / mesh
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    data: int = 16
    model: int = 16
    pod: int = 1

    @property
    def multi_pod(self) -> bool:
        return self.pod > 1

    @property
    def num_devices(self) -> int:
        return self.data * self.model * self.pod


@dataclass(frozen=True)
class ShardingConfig:
    """Logical-axis -> mesh-axis rules. None = replicated."""
    batch: Tuple[str, ...] = ("pod", "data")
    heads: Optional[str] = "model"
    mlp: Optional[str] = "model"
    vocab: Optional[str] = "model"
    embed: Optional[str] = None
    cache_seq: Optional[str] = None      # set to "data" for long_500k
    experts: Optional[str] = None        # "model" for expert-parallel variant
    seq: Optional[str] = None            # sequence/context parallel activations


# ---------------------------------------------------------------------------
# Input shapes (assignment block)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str       # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


# TPU v5e hardware constants for the roofline analysis.
@dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12      # FLOP/s per chip
    hbm_bandwidth: float = 819e9         # bytes/s per chip
    ici_bandwidth: float = 50e9          # bytes/s per link
    hbm_bytes: float = 16e9              # capacity per chip
    vmem_bytes: float = 128 * 2**20


TPU_V5E = HardwareSpec()
