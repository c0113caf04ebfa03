"""Configuration system for the xGR reproduction framework.

A single flat, frozen ``ModelConfig`` describes every supported architecture
family (dense GQA / MLA / MoE / SSM / hybrid / enc-dec / VLM).  Architecture
presets live in ``repro.configs`` (one module per assigned architecture, each
citing its source).  ``GRConfig`` carries the generative-recommendation
serving parameters (beam width, Top-K, number of decode phases) from the
paper; ``TrainConfig`` / ``ServeConfig`` configure the substrate drivers.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.

    ``family`` selects the backbone implementation:
      - ``dense``  : decoder-only transformer (GQA or MLA attention)
      - ``moe``    : decoder-only transformer with routed-expert FFNs
      - ``ssm``    : attention-free RWKV6 stack
      - ``hybrid`` : Mamba2 backbone with a shared attention block (Zamba2)
      - ``encdec`` : encoder-decoder with cross attention (Whisper)
      - ``vlm``    : decoder-only backbone consuming interleaved text tokens
                     and precomputed vision patch embeddings (Qwen2-VL)
    """

    name: str
    family: str
    source: str                      # citation: arXiv id or HF model card

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads

    # --- attention flavour -------------------------------------------------
    attention_kind: str = "gqa"      # "gqa" | "mla" | "none"
    qkv_bias: bool = False
    rope_kind: str = "rope"          # "rope" | "mrope" | "none" | "learned"
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0       # partial rotary (stablelm: 0.25)
    norm_kind: str = "rmsnorm"       # "rmsnorm" | "layernorm"
    act_kind: str = "swiglu"         # "swiglu" | "gelu"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    max_position: int = 131072

    # --- MLA (multi-head latent attention) ---------------------------------
    mla_q_lora_rank: int = 0         # 0 -> full-rank q projection
    mla_kv_lora_rank: int = 0
    mla_qk_nope_head_dim: int = 0
    mla_qk_rope_head_dim: int = 0
    mla_v_head_dim: int = 0

    # --- MoE ----------------------------------------------------------------
    moe_num_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden size
    moe_num_shared_experts: int = 0  # deepseek shared experts
    moe_first_dense_layers: int = 0  # leading dense layers (deepseek-v2: 1)
    moe_dense_residual: bool = False # arctic: parallel dense FFN residual
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.001

    # --- SSM / RWKV ----------------------------------------------------------
    ssm_state_dim: int = 0           # mamba2 d_state / rwkv head_size
    ssm_head_dim: int = 64
    ssm_expand: int = 2              # mamba2 expansion factor
    ssm_conv_width: int = 4

    # --- hybrid (zamba2) ------------------------------------------------------
    hybrid_attn_every: int = 6       # a shared attention block every N mamba blocks

    # --- encoder-decoder (whisper) --------------------------------------------
    encoder_layers: int = 0
    encoder_seq: int = 1500          # max audio frames after the (stubbed) conv frontend
    frontend_dim: int = 0            # stubbed frontend output dim (0 -> d_model)

    # --- vlm (qwen2-vl) --------------------------------------------------------
    vision_tokens: int = 0           # stub patch-embedding token budget per sample

    # --- long-context serving variant -----------------------------------------
    sliding_window: int = 0          # 0 -> full attention; >0 -> window for long decode

    # -----------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_moe(self) -> bool:
        return self.moe_num_experts > 0

    @property
    def n_params(self) -> int:
        """Approximate parameter count (embedding + blocks + head)."""
        d, v = self.d_model, self.vocab_size
        emb = v * d * (1 if self.tie_embeddings else 2)
        per_layer = self._per_layer_params()
        n = emb + self.num_layers * per_layer
        if self.family == "encdec":
            enc_layer = 4 * d * d + 2 * d * self.d_ff  # self-attn + mlp
            n += self.encoder_layers * enc_layer
            n += self.num_layers * (4 * d * d)         # cross attention
        if self.family == "hybrid":
            hd = self.resolved_head_dim
            n += 4 * d * d + 2 * d * d                 # one shared attn block (reused)
        return n

    def _per_layer_params(self) -> int:
        d = self.d_model
        hd = self.resolved_head_dim
        if self.attention_kind == "mla":
            r = self.mla_kv_lora_rank
            qh = self.mla_qk_nope_head_dim + self.mla_qk_rope_head_dim
            attn = (d * (self.mla_q_lora_rank or d)
                    + (self.mla_q_lora_rank or 0) * self.num_heads * qh
                    + d * (r + self.mla_qk_rope_head_dim)
                    + r * self.num_heads * (self.mla_qk_nope_head_dim + self.mla_v_head_dim)
                    + self.num_heads * self.mla_v_head_dim * d)
        elif self.attention_kind == "none":
            attn = 6 * d * d  # rwkv time-mix approximation
        else:
            attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd \
                   + self.num_heads * hd * d
        if self.is_moe:
            ff_active = 3 * d * self.moe_d_ff * self.moe_num_experts
            ff_active += 3 * d * self.moe_d_ff * self.moe_num_shared_experts
            if self.moe_dense_residual:
                ff_active += 3 * d * self.d_ff
            ff = ff_active + d * self.moe_num_experts  # router
        else:
            mult = 3 if self.act_kind == "swiglu" else 2
            ff = mult * d * self.d_ff
        return attn + ff

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: only routed top-k experts)."""
        if not self.is_moe:
            return self.n_params
        d = self.d_model
        per_layer_moe = 3 * d * self.moe_d_ff * self.moe_num_experts
        per_layer_active = 3 * d * self.moe_d_ff * self.moe_top_k
        return self.n_params - self.num_layers * (per_layer_moe - per_layer_active)

    # -----------------------------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests.

        2 layers, d_model <= 512, <= 4 experts — per the assignment contract.
        """
        small_heads = max(2, min(4, self.num_heads))
        ratio = max(1, self.num_heads // max(1, self.num_kv_heads))
        small_kv = max(1, small_heads // min(ratio, small_heads))
        updates = dict(
            name=self.name + "-reduced",
            num_layers=2,
            d_model=256,
            num_heads=small_heads,
            num_kv_heads=small_kv,
            head_dim=64,
            d_ff=512,
            vocab_size=min(self.vocab_size, 1024),
            max_position=4096,
            encoder_seq=min(self.encoder_seq, 64),
            vision_tokens=min(self.vision_tokens, 16),
            sliding_window=min(self.sliding_window, 128) if self.sliding_window else 0,
        )
        if self.attention_kind == "mla":
            updates.update(
                mla_q_lora_rank=64 if self.mla_q_lora_rank else 0,
                mla_kv_lora_rank=32,
                mla_qk_nope_head_dim=32,
                mla_qk_rope_head_dim=16,
                mla_v_head_dim=32,
            )
        if self.is_moe:
            updates.update(
                moe_num_experts=4,
                moe_top_k=min(2, self.moe_top_k),
                moe_d_ff=256,
                moe_num_shared_experts=min(1, self.moe_num_shared_experts),
                moe_first_dense_layers=min(1, self.moe_first_dense_layers),
            )
        if self.family in ("ssm", "hybrid"):
            updates.update(ssm_state_dim=min(self.ssm_state_dim or 64, 64),
                           ssm_head_dim=32, hybrid_attn_every=2)
        if self.family == "encdec":
            updates.update(encoder_layers=2)
        return dataclasses.replace(self, **updates)


# ---------------------------------------------------------------------------
# Generative-recommendation serving parameters (the paper's workload)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GRConfig:
    """xGR serving parameters (paper §2.2, §5, §6)."""

    beam_width: int = 128            # BW
    top_k: int = 128                 # per-beam Top-K
    num_decode_phases: int = 3       # ND: token-ID triplet == one item id
    num_items: int = 100_000         # valid item catalog size
    tid_vocab: int = 8192            # per-level token-id vocabulary
    length_penalty: float = 0.0
    mask_neg: float = -1e9           # additive mask value for invalid tokens
    #: beam-expansion algorithm (paper §6 early sorting termination):
    #:   "dense"  — mask the full (R, BW, V) grid, two-stage Top-K over V
    #:   "sparse" — gather logits at each beam's trie children (padded-CSR
    #:              tables) and Top-K over the (R, BW, max_fanout) pool;
    #:              selection-equivalent to "dense", requires an ItemTrie
    beam_select: str = "dense"
    #: on-device early-termination select (paper §6 Fig 11, DESIGN.md §11):
    #: between the two top-k stages, compute the running global bar (BW-th
    #: best so far across per-beam descending top-K columns) and floor
    #: candidates strictly below it before the stage-2 sort.  Selection is
    #: bit-identical; pruning counts surface as ``BeamState.pruned`` and in
    #: ``ServerReport.beam_pool``.
    beam_early_term: bool = False


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    batch_size: int = 8
    seq_len: int = 512
    remat: bool = True
    seed: int = 0


@dataclass(frozen=True)
class ServeConfig:
    """xSchedule parameters (paper §7)."""

    max_batch_tokens: int = 65536    # token-capacity dynamic batching
    max_batch_requests: int = 64
    slo_ms: float = 200.0            # P99 SLO
    batch_wait_quota_ms: float = 5.0 # max batching delay before forced dispatch
    num_streams: int = 4             # engine concurrency (multi-stream analogue)
    graph_dispatch: bool = True      # jit whole decode loop as one program
    scheduler_policy: str = "token-capacity"  # see serving.scheduler registry
    #: per-step token budget of the "chunked" mixed prefill/decode policy:
    #: each engine step packs decode steps first, then prefill chunks, and
    #: never exceeds this many tokens (paper §5 staged prefill)
    prefill_chunk_tokens: int = 256
    #: beam-select override for the engine: "" keeps GRConfig.beam_select,
    #: "dense"/"sparse" force that path (see GRConfig.beam_select)
    beam_select: str = ""
    #: step executor for continuous (chunked) serving (ISSUE 5):
    #:   "sequential" — one blocked dispatch per StepPlan entry (reference)
    #:   "pipelined"  — same-phase decode entries fuse into ONE batched
    #:                  dispatch, prefill chunks stage through round-robin
    #:                  input lanes, and the step syncs once at its end
    #: (``repro.serving.make_engine`` interprets this; results are
    #: bit-identical between the two)
    executor: str = "sequential"
    #: tokens per page of the shared-KV arena backing continuous serving
    #: (0 = the arena default; keep it a divisor of the 64-token minimum
    #: prompt bucket so spans are whole pages)
    kv_page_tokens: int = 0
    #: initial shared-KV arena pages (0 = small auto default; the arena
    #: grows on demand, preserving live pages)
    kv_arena_pages: int = 0
    #: cross-request hierarchical KV prefix cache (ISSUE 6): hash prompt
    #: prefixes at page granularity to refcounted shared page runs, so a
    #: warm re-request adopts cached pages and skips those prefill chunks
    #: entirely (copy-on-write at the divergence page; bit-identical
    #: outputs).  Continuous ("chunked") scheduling only.
    prefix_cache: bool = False
    #: host-RAM budget (bytes) for the prefix cache's spill tier: device
    #: pages evicted under pool pressure move here LRU and fault back in
    #: on a hit.  0 = no spill tier (evicted pages are recomputed).
    host_spill_bytes: int = 0
    #: data-parallel serving replicas (ISSUE 7): the ServingSystem routes
    #: submits across this many addressable replicas, each owning its own
    #: engine, KV arena, prefix cache, and scheduler state over a disjoint
    #: device-mesh slice.  1 = today's single-engine system.
    num_replicas: int = 1
    #: tensor-parallel degree per replica (the 'model' mesh axis): attention
    #: heads and FFN hidden shard per sharding/specs.py.  1 with
    #: num_replicas=1 keeps the exact unsharded single-device code path.
    model_axis: int = 1
    #: attention implementation override for engines built without an
    #: explicit EngineSpec (ISSUE 8): "" keeps the caller/spec default;
    #: "staged"/"paged"/"kernel" force that path.  "kernel" + the pipelined
    #: arena path runs the fused paged Pallas kernel — decode reads the
    #: page pool in place, no gathered contiguous view (DESIGN.md §11).
    attention_impl: str = ""
    #: enable GRConfig.beam_early_term on the engine's beam select
    #: (bit-identical selections; pruning stats in ServerReport.beam_pool)
    beam_early_term: bool = False
    #: overload control (ISSUE 9, DESIGN.md §12):
    #:   "none"    — accept everything unconditionally (the pre-overload
    #:               behavior, bit-identical outputs)
    #:   "reject"  — admission control + queue shedding: a per-replica cost
    #:               model (EWMA-calibrated from measured step timings)
    #:               predicts completion at submit; requests predicted past
    #:               their deadline get a typed ``ServeResult(
    #:               status="rejected")``, and queued requests past
    #:               ``queue_timeout_ms`` (or their deadline) are shed at
    #:               plan time instead of dispatched dead
    #:   "degrade" — "reject" plus graceful degradation: over-budget
    #:               in-flight requests finish early at a phase boundary
    #:               (phase truncation) and serve a top-BW' slice of the
    #:               same beam state (exact subset of the full-width
    #:               selection), recorded per request
    shed_policy: str = "none"
    #: shed queued requests older than this (milliseconds, simulated clock)
    #: at plan time; 0 = never shed by age (deadline shedding still applies
    #: when shed_policy != "none")
    queue_timeout_ms: float = 0.0
    #: safety factor on the admission cost model's completion prediction —
    #: >1 rejects earlier (protects admitted requests' deadlines at the
    #: cost of goodput near the boundary)
    admission_margin: float = 1.2
    #: beam width served by a degraded request (top rows of the SAME beam
    #: state — an exact subset of the full-width selection); 0 = BW // 2
    degrade_beam_width: int = 0
    #: flight recorder: record span/counter telemetry at every lifecycle
    #: point into ``ServingSystem.tracer``, on the wall clock.  Off by
    #: default; on or off, the stack makes the same device syncs and the
    #: same scheduling/selection decisions (timestamps are only read)
    trace: bool = False
    #: ring-buffer capacity (events) of the flight recorder
    trace_capacity: int = 262144


@dataclass(frozen=True)
class EngineSpec:
    """Single point of execution choice for the engine (ISSUE 1 tentpole).

    ``backend`` names an :class:`~repro.core.gr_decode.ExecutionBackend`
    ("graph" = whole generate loop as one jitted program, "eager" =
    per-phase dispatch with host mask generation).  ``host_overlap`` models
    xSchedule's overlap of host mask generation with the device forward
    pass on the eager path.
    """

    backend: str = "graph"           # "graph" | "eager"
    attention_impl: str = "staged"   # "staged" | "paged" | "kernel"
    num_streams: int = 4
    host_overlap: bool = True
    #: "" = inherit GRConfig.beam_select; "dense"/"sparse" override it
    beam_select: str = ""

    def __post_init__(self):
        if self.backend not in ("graph", "eager"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.attention_impl not in ("staged", "paged", "kernel"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.beam_select not in ("", "dense", "sparse"):
            raise ValueError(f"unknown beam_select {self.beam_select!r}")

    @classmethod
    def from_serve_config(cls, serve_cfg: "ServeConfig",
                          attention_impl: str = "staged") -> "EngineSpec":
        """Map the legacy ``graph_dispatch`` flag onto a backend name.

        ``ServeConfig.attention_impl`` (when non-empty) wins over the
        ``attention_impl`` argument, mirroring ``beam_select``."""
        return cls(backend="graph" if serve_cfg.graph_dispatch else "eager",
                   attention_impl=serve_cfg.attention_impl or attention_impl,
                   num_streams=serve_cfg.num_streams,
                   host_overlap=serve_cfg.num_streams > 1,
                   beam_select=serve_cfg.beam_select)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # "train" | "prefill" | "decode"


INPUT_SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec("train_4k", 4_096, 256, "train"),
    ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    ShapeSpec("decode_32k", 32_768, 128, "decode"),
    ShapeSpec("long_500k", 524_288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in INPUT_SHAPES}


def get_shape(name: str) -> ShapeSpec:
    try:
        return SHAPES_BY_NAME[name]
    except KeyError:
        raise KeyError(f"unknown input shape {name!r}; "
                       f"have {sorted(SHAPES_BY_NAME)}") from None
