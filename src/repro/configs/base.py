"""Configuration system.

Every assigned architecture is a frozen dataclass instance built by one
``src/repro/configs/<id>.py`` module. Configs are pure data: models,
sharding, and the launcher all key off these fields. ``reduced()`` derives
the CPU smoke-test variant of any config (same family/topology, tiny dims).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Input shapes (assigned cells). Every arch is paired with all four; cells
# that are inapplicable for a family are resolved by `cells_for()` below.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                 # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int

    def __post_init__(self):
        assert self.kind in ("train", "prefill", "decode"), self.kind


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    # capacity factor for the one-hot dispatch path (tokens per expert =
    # capacity_factor * tokens * top_k / num_experts). The dry-run uses the
    # einsum dispatch which is capacity-free; this is kept for the serving
    # batcher.
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters (exact assigned numbers live in the
    per-arch modules)."""

    name: str
    family: str               # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int            # 0 for attention-free families
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0         # 0 -> d_model // num_heads
    qkv_bias: bool = False
    mlp_kind: str = "swiglu"  # swiglu | gelu
    norm_kind: str = "rmsnorm"  # rmsnorm | layernorm
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    sliding_window: int = 0   # 0 -> full attention; >0 -> SWA window

    # MoE
    moe: Optional[MoEConfig] = None

    # hybrid (recurrentgemma): block pattern cycled over layers
    block_pattern: Tuple[str, ...] = ()   # e.g. ("rglru", "rglru", "local_attn")
    local_window: int = 2048
    lru_width: int = 0        # 0 -> d_model
    conv1d_width: int = 4     # temporal conv in recurrent block

    # ssm (rwkv6)
    rwkv_head_size: int = 64
    rwkv_decay_lora: int = 64
    rwkv_mix_lora: int = 32

    # encoder-decoder (whisper): encoder depth == num_layers, plus frontend
    # stub that feeds (batch, num_frames, d_model) embeddings.
    encoder_layers: int = 0
    num_frames: int = 1500

    # vlm (llava): patch-embedding prefix length (anyres: 5 tiles x 576)
    num_patches: int = 0

    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    # notes for DESIGN/EXPERIMENTS provenance
    source: str = ""

    def __post_init__(self):
        assert self.family in ("dense", "moe", "ssm", "hybrid", "encdec", "vlm")
        if self.family == "moe":
            assert self.moe is not None
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # -- derived quantities -------------------------------------------------

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """True if decode state is O(window) / O(1) rather than O(seq)."""
        if self.family in ("ssm",):
            return True
        if self.family == "hybrid":
            return True            # RG-LRU state + bounded local-attn window
        return self.sliding_window > 0

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS and napkin math)."""
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        hd = self.head_dim
        n = 0
        # embeddings (+ untied output head)
        n += v * d * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":
            per = 0
            per += 5 * d * d                      # r,k,v,g,o projections (w via lora)
            per += d * self.rwkv_decay_lora * 2   # decay lora
            per += 5 * (d * self.rwkv_mix_lora * 2)  # token-shift mix loras
            per += 7 * d                          # mix biases / decay base / bonus
            per += 2 * d * f + d * d              # channel mix k,v,r
            per += 2 * d                          # norms
            return n + L * per
        att = d * (self.num_heads * hd) + d * (self.num_kv_heads * hd) * 2 \
            + (self.num_heads * hd) * d
        if self.qkv_bias:
            att += self.num_heads * hd + 2 * self.num_kv_heads * hd
        mlp = (3 if self.mlp_kind == "swiglu" else 2) * d * f
        if self.family == "moe":
            mlp_total = self.moe.num_experts * mlp + d * self.moe.num_experts
        else:
            mlp_total = mlp
        if self.family == "hybrid":
            lw = self.lru_width or d
            rec = 2 * d * lw + lw * d + self.conv1d_width * lw + 3 * lw \
                + 2 * (lw * max(lw // 8, 1))      # gates are block-diagonal LoRA-ish
            pat = self.block_pattern or ("rglru",)
            n_attn = sum(1 for i in range(L) if pat[i % len(pat)] == "local_attn")
            n_rec = L - n_attn
            return n + n_attn * (att + mlp + 2 * d) + n_rec * (rec + mlp + 2 * d)
        per = att + mlp_total + 2 * d
        total = n + L * per
        if self.family == "encdec":
            # encoder layers + decoder cross-attention
            total += self.encoder_layers * (att + mlp + 2 * d)
            total += L * (att + d)                # cross-attn per decoder layer
        return total

    def active_param_count(self) -> int:
        """Active (per-token) params — differs from total only for MoE."""
        if self.family != "moe":
            return self.param_count()
        d, f, L = self.d_model, self.d_ff, self.num_layers
        mlp = 3 * d * f
        inactive = L * (self.moe.num_experts - self.moe.top_k) * mlp
        return self.param_count() - inactive


@dataclass(frozen=True)
class CommConfig:
    """TAC — the paper's technique (see DESIGN.md §2).

    mode:
      gspmd      — pure GSPMD auto sharding; XLA owns all collectives
                   ("the kernel network stack").
      sockets    — explicit per-tensor psum over the DP axes
                   (plain-sockets baseline: one op per tensor).
      vma        — one monolithic fused psum of the whole flattened grad
                   (libvma analogue: minimal op count, no overlap, peak mem).
      hadronio   — gathering-write aggregation: pack into ring-buffer slices,
                   one psum per slice (paper-faithful).
      hadronio_rs— beyond-paper: per-slice reduce-scatter + all-gather with
                   data-sharded (ZeRO-1) optimizer update.
      hadronio_overlap — beyond-paper: DDP-style reverse-layer bucketing;
                   per-bucket collectives depend only on their own leaves
                   so they overlap the remaining backward compute.
      hadronio_overlap_rs — beyond-paper: bucketed ZeRO-1; each bucket
                   reduce-scatters its own shard (same overlap property)
                   and the optimizer updates flat data-sharded moments.

    ``pack`` selects the pack/cast/error-feedback copy-path implementation
    (the paper's gathering-write hot spot): ``jnp`` (reference) or
    ``pallas`` (fused one-pass kernel, kernels/ring_pack.py). The same switch
    selects the unpack-stage implementation (the scattering-read epilogue
    — one fused cast-from-wire-dtype pass over the collective results).

    ``aggregate`` is the wire-flush granularity of the channel schedule
    (paper §III-C: hadroNIO's ring buffer merges many small writes into
    one large UCX request per connection):

      slice   — one collective per ring slice / bucket; same-channel
                collectives are chained in order.
      channel — gathering write at connection granularity: every slice
                assigned to a channel is coalesced into ONE contiguous
                wire buffer and flushed with a single collective per
                channel. Bit-identical numerics; the reduce-scatter
                flush interleaves per-slice shard chunks so the ZeRO-1
                flat-shard ordering is unchanged.

    ``flush`` is the channel SCHEDULE (core/flush_scheduler.py —
    hadroNIO flushes a connection the moment the selector reports it
    writable, §III-B, instead of at a global barrier):

      step    — slices/buckets land on channels round-robin and every
                coalesced flush is emitted in one end-of-exchange loop.
      ready   — flush-when-ready: buckets are grouped onto channels
                contiguously in gradient-production (reverse-layer)
                order and each channel's flush is emitted the moment its
                last bucket is staged — mid-backward, so under
                ``aggregate="channel"`` with channels < n_buckets the
                overlap modes keep per-channel independence that the
                ``step`` schedule forfeits. Bit-identical numerics (the
                schedule moves the same bytes; only the emission
                structure changes).

    Modes without a channel schedule (gspmd / sockets / vma) have nothing
    to coalesce; ``aggregate`` and ``flush`` are documented no-ops there
    (unlike ``compress``, they never change numerics, so no rejection is
    needed).

    The authoritative mode list is the backend registry
    (``repro.core.backends.available_modes``) — new modes register
    themselves and need no edit here.
    """

    mode: str = "gspmd"
    ring_capacity_bytes: int = 256 * 1024 * 1024
    slice_bytes: int = 4 * 1024 * 1024
    channels: int = 4                  # in-flight slices ("connections")
    compress: str = "none"             # none | bf16 | int8_ef
    pack: str = "jnp"                  # pack/unpack-stage impl: jnp | pallas
    aggregate: str = "slice"           # wire-flush granularity: slice | channel
    flush: str = "step"                # channel schedule: step | ready
    hierarchical: bool = True          # pod-aware two-level collectives
    leader_channels: int = 1           # channels carved for cross-pod traffic
    #   Under pod-aware hierarchical emission with aggregate="channel",
    #   the LAST ``leader_channels`` channels of the pool are the leader
    #   lanes: intra-pod stages ride the remaining (local) lanes and only
    #   the 1/n_pod-reduced shards are coalesced onto leader lanes for the
    #   cross-pod collective (UCX multi-rail: the scarce link gets its own
    #   dedicated connections). Clamped at emission time to pool-1 so a
    #   1-channel pool stays flat; ServeConfig validates the strict form
    #   when pods are actually configured.

    COMPRESS_CODECS = ("none", "bf16", "int8_ef")
    PACK_IMPLS = ("jnp", "pallas")
    AGGREGATES = ("slice", "channel")
    FLUSHES = ("step", "ready")

    def __post_init__(self):
        # the backend registry is the single source of truth for modes
        # (lazy import: backends import this module for the dataclass)
        from repro.core.backends import available_modes
        assert self.mode in available_modes(), \
            f"unknown comm mode {self.mode!r}; registered: {available_modes()}"
        if self.channels < 1:
            raise ValueError(
                f"comm.channels must be >= 1 (got {self.channels}): the "
                "connection pool needs at least one channel; values above "
                "n_slices are clamped to fully-independent emission")
        if self.compress not in self.COMPRESS_CODECS:
            raise ValueError(
                f"unknown comm.compress {self.compress!r}: expected one of "
                f"{self.COMPRESS_CODECS}")
        if self.pack not in self.PACK_IMPLS:
            raise ValueError(
                f"unknown comm.pack {self.pack!r}: expected one of "
                f"{self.PACK_IMPLS}")
        if self.aggregate not in self.AGGREGATES:
            raise ValueError(
                f"unknown comm.aggregate {self.aggregate!r}: expected one "
                f"of {self.AGGREGATES} ('channel' coalesces every slice on "
                "a channel into one wire flush per collective)")
        if self.flush not in self.FLUSHES:
            raise ValueError(
                f"unknown comm.flush {self.flush!r}: expected one of "
                f"{self.FLUSHES} ('ready' emits each channel's flush the "
                "moment its last assigned bucket is staged; 'step' flushes "
                "every channel at one end-of-exchange loop)")
        if self.leader_channels < 1:
            raise ValueError(
                f"comm.leader_channels must be >= 1 (got "
                f"{self.leader_channels}): the cross-pod stage of the "
                "hierarchical emission needs at least one dedicated lane; "
                "values >= comm.channels are clamped to channels-1 at "
                "emission time (a 1-channel pool has no lane to carve)")
        assert self.slice_bytes > 0 and self.ring_capacity_bytes >= self.slice_bytes


@dataclass(frozen=True)
class TenantConfig:
    """One tenant of a multi-tenant ``EventLoopGroup``: a named model
    family sharing the group's channel pool with the others. Tenants
    partition ``serve.event_loops`` into disjoint contiguous loop
    ranges (declaration order), so channel ownership stays disjoint
    per loop AND per tenant; ``weight`` sets the tenant's share of the
    group-level admission via deterministic weighted-fair scheduling
    (docs/FAMILIES.md §Tenants and fairness)."""

    name: str                          # unique tenant key (Request.tenant)
    arch: str = ""                     # registry arch served for this tenant
    weight: int = 1                    # weighted-fair admission share
    event_loops: int = 1               # loops owned by this tenant


@dataclass(frozen=True)
class ServeConfig:
    """Event-loop serving (the paper's §IV benchmark topology, applied to
    inference): an ``EventLoopGroup`` of ``event_loops`` loops, each
    owning a DISJOINT contiguous run of the ``comm.channels`` pool
    (Ibdxnet's per-thread connection ownership) and a run queue of
    in-flight requests; new requests are admitted at flush boundaries
    (continuous batching). ``poll`` mirrors hadroNIO's completion
    polling:

      busy     — spin on readiness; lowest latency, one core per loop
                 (the paper's busy-polling optimization).
      park     — block until complete (the epoll / selector.select
                 fallback).
      adaptive — spin for ``spin_us`` then park (hadroNIO's bounded
                 busy-poll before yielding).

    ``comm`` is the SAME config the training path uses — serving
    collectives (KV gathering writes, tensor-parallel logit reductions)
    flow through the registered CommBackend's wire path, so
    mode/channels/slice_bytes/aggregate/flush all apply to inference
    traffic (see docs/SERVING.md). Serving payloads are activations, not
    gradients: wire compression (an error-feedback feature) is rejected
    by the dispatch layer.

    ``pods`` configures the two-level serving fabric (docs/SERVING.md
    §Topology): the serve mesh becomes ``(pods, devices//pods)`` over
    ``(pod_axis, "data")``, and with ``comm.hierarchical`` the emission
    decomposes so intra-pod traffic rides local channels and only the
    1/n_pod-reduced shards cross pods on the ``comm.leader_channels``
    leader lanes, which are pinned to the first ``leader_loops`` event
    loops (topology-aware channel affinity). ``pods`` must divide the
    device count — validated where the devices are known
    (``launch/mesh.make_serve_mesh``), not here.
    """

    event_loops: int = 1
    poll: str = "busy"                 # busy | park | adaptive
    spin_us: float = 50.0              # adaptive: spin budget before parking
    max_batch: int = 8                 # decode slots per event loop
    max_len: int = 256                 # prompt + generation bound (KV alloc)
    comm: CommConfig = field(default_factory=CommConfig)
    pods: int = 1                      # two-level fabric: pod count
    pod_axis: str = "pod"              # mesh axis name of the pod dimension
    leader_loops: int = 1              # loops pinned to the leader lanes
    tenants: tuple = ()                # TenantConfig partition of the loops

    POLLS = ("busy", "park", "adaptive")

    def __post_init__(self):
        if self.event_loops < 1:
            raise ValueError(
                f"serve.event_loops must be >= 1 (got {self.event_loops})")
        if self.poll not in self.POLLS:
            raise ValueError(
                f"unknown serve.poll {self.poll!r}: expected one of "
                f"{self.POLLS} (busy spins, park blocks, adaptive spins "
                f"for spin_us then parks)")
        if self.event_loops > self.comm.channels:
            raise ValueError(
                f"serve.event_loops={self.event_loops} exceeds "
                f"comm.channels={self.comm.channels}: each event loop "
                "must OWN a disjoint non-empty run of the channel pool "
                "(raise comm.channels or lower event_loops)")
        if self.spin_us < 0:
            raise ValueError(f"serve.spin_us must be >= 0 ({self.spin_us})")
        if self.pods < 1:
            raise ValueError(f"serve.pods must be >= 1 (got {self.pods})")
        if not self.pod_axis:
            raise ValueError("serve.pod_axis must be a non-empty axis name")
        if not 1 <= self.leader_loops <= self.event_loops:
            raise ValueError(
                f"serve.leader_loops={self.leader_loops} must be in "
                f"[1, event_loops={self.event_loops}]: leader channels are "
                "pinned to a designated subset of the loops, and at least "
                "one loop must carry the cross-pod lanes")
        if self.pods > 1 and self.comm.hierarchical:
            if self.comm.leader_channels >= self.comm.channels:
                raise ValueError(
                    f"comm.leader_channels={self.comm.leader_channels} must "
                    f"be < comm.channels={self.comm.channels} when serving "
                    f"{self.pods} pods hierarchically: carving every lane "
                    "for cross-pod traffic leaves no local lane for the "
                    "in-pod stages (raise comm.channels or lower "
                    "leader_channels)")
            if self.event_loops > self.comm.channels - self.comm.leader_channels:
                raise ValueError(
                    f"serve.event_loops={self.event_loops} exceeds the "
                    f"{self.comm.channels - self.comm.leader_channels} "
                    f"LOCAL channels (channels={self.comm.channels} minus "
                    f"leader_channels={self.comm.leader_channels}): under "
                    "the two-level fabric every loop must own at least one "
                    "local lane for its in-pod stages")
        if self.tenants:
            names = [t.name for t in self.tenants]
            if any(not n for n in names) or len(set(names)) != len(names):
                raise ValueError(
                    f"serve.tenants names must be unique and non-empty "
                    f"(got {names!r}): Request.tenant routes by name")
            for t in self.tenants:
                if t.weight < 1:
                    raise ValueError(
                        f"tenant {t.name!r}: weight must be >= 1 (got "
                        f"{t.weight}) — zero-weight tenants would starve")
                if t.event_loops < 1:
                    raise ValueError(
                        f"tenant {t.name!r}: event_loops must be >= 1 (got "
                        f"{t.event_loops}): every tenant needs at least one "
                        "loop, hence at least one owned channel")
            total = sum(t.event_loops for t in self.tenants)
            if total != self.event_loops:
                raise ValueError(
                    f"serve.tenants pin the fleet size: per-tenant "
                    f"event_loops sum to {total} but serve.event_loops="
                    f"{self.event_loops}. Tenant loop ranges are a static "
                    "partition of the group, so supervisor autoscaling "
                    "requires tenants=()")


@dataclass(frozen=True)
class RunConfig:
    """Everything a launcher needs beyond the model itself."""

    model: ModelConfig
    shape: ShapeConfig
    comm: CommConfig = field(default_factory=CommConfig)

    # optimizer
    lr: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1000
    microbatches: int = 1              # gradient accumulation

    # checkpointing / fault tolerance
    checkpoint_dir: str = ""
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
    async_checkpoint: bool = True
    max_restarts: int = 100

    # data
    data_path: str = ""                # empty -> synthetic
    data_seed: int = 0

    seed: int = 0


# ---------------------------------------------------------------------------
# Cell applicability (DESIGN.md §5)
# ---------------------------------------------------------------------------


def cell_skip_reason(model: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    """Return a reason string if (model, shape) is an assigned-but-skipped
    cell, else None. Mirrors the brief: ``long_500k`` needs sub-quadratic
    attention; encoder-only archs have no decode step (none assigned)."""
    if shape.name == "long_500k" and not model.sub_quadratic:
        return ("pure full attention: 500k-token decode requires a 500k KV "
                "cache and O(seq) attention per step — skipped per brief")
    return None


def cells_for(model: ModelConfig) -> list[ShapeConfig]:
    return [s for s in SHAPES.values() if cell_skip_reason(model, s) is None]


# ---------------------------------------------------------------------------
# Reduced (smoke) variants — same family/topology, tiny dims.
# ---------------------------------------------------------------------------


def reduced(cfg: ModelConfig) -> ModelConfig:
    """A CPU-runnable config of the same family: few layers, small width,
    few experts, tiny vocab — exercises every code path of the family."""
    num_heads = min(cfg.num_heads, 4) if cfg.num_heads else 0
    num_kv = max(1, min(cfg.num_kv_heads, num_heads)) if num_heads else 0
    kw = dict(
        name=cfg.name + "-reduced",
        num_layers=min(cfg.num_layers, 4 if not cfg.block_pattern else 2 * len(cfg.block_pattern)),
        d_model=64,
        num_heads=num_heads,
        num_kv_heads=num_kv,
        head_dim=16 if num_heads else 0,
        d_ff=128,
        vocab_size=256,
        lru_width=64 if cfg.family == "hybrid" else 0,
        rwkv_head_size=16,
        rwkv_decay_lora=8,
        rwkv_mix_lora=8,
        encoder_layers=min(cfg.encoder_layers, 2),
        num_frames=8,
        num_patches=min(cfg.num_patches, 8),
        sliding_window=min(cfg.sliding_window, 16) if cfg.sliding_window else 0,
        local_window=16,
        param_dtype="float32",
        compute_dtype="float32",
    )
    if cfg.moe is not None:
        # capacity_factor = num_experts makes reduced configs dropless
        # (capacity >= tokens*k), so prefill/decode consistency is exact;
        # full configs keep the production 1.25.
        kw["moe"] = MoEConfig(num_experts=min(cfg.moe.num_experts, 4),
                              top_k=min(cfg.moe.top_k, 2),
                              capacity_factor=float(
                                  min(cfg.moe.num_experts, 4)))
    return replace(cfg, **kw)


def describe(cfg: ModelConfig) -> str:
    n = cfg.param_count()
    a = cfg.active_param_count()
    extra = f" (active {a/1e9:.2f}B)" if a != n else ""
    return f"{cfg.name}: {cfg.family}, {cfg.num_layers}L d={cfg.d_model} " \
           f"ff={cfg.d_ff} vocab={cfg.vocab_size} -> {n/1e9:.2f}B params{extra}"
