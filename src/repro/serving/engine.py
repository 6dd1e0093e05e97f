"""Batched decode engine: KV-cache manager + request batcher + sampler.

The netty analogy carries over (DESIGN.md §2): requests are connections,
the engine's fixed-size decode batch is the worker pool, and admission is
round-robin like the paper's benchmark assigns connections to selectors.

Mechanics:

* Attention-family archs: prompts are **right-padded** to the bucket
  length and tracked with per-request ``pos`` vectors — pad slots are
  never attended (validity mask ``j <= pos``) and the first generated
  token overwrites the first pad slot, so mixed-length batches are exact.
* Recurrent archs (ssm / hybrid): the recurrence would absorb pad tokens,
  so the batcher groups requests into *equal-length* buckets (exact, no
  pads) — noted limitation vs. paged attention, acceptable at this scope.
* Sampling: greedy or temperature; stop on ``eos_id`` or ``max_new``.
* **Continuous batching**: the engine runs ``max_batch`` decode SLOTS.
  When a request finishes, its slot is freed and the next request from
  the run queue is admitted AT THE FLUSH BOUNDARY (the decode-step
  boundary where the staged emission's channel flushes have completed
  and been polled): it is prefilled solo (exactness is per-row, so solo
  and batched prefill agree bit-for-bit), its cache rows are written
  into the freed slot, and it decodes alongside the residents.
* **Serving through the comm stack**: constructed with a
  :class:`~repro.configs.base.ServeConfig`, the engine's prefill/decode
  steps come from ``serving/dispatch.py`` — KV gathering writes and
  tensor-parallel logit reductions flow through the registered
  CommBackend wire (staged emission API), honoring the owning event
  loop's channel affinity. Completion waits go through the loop's
  :class:`~repro.serving.event_loop.Poller` (busy/park/adaptive).
"""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict, deque
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.models import api
from repro.obs import trace as obs_trace
from repro.models.layers import no_shard
from repro.serving import dispatch
from repro.serving.event_loop import (EventLoop, EventLoopGroup, Poller,
                                      channel_affinity)

PyTree = Any

ADMIT_PAD = 16      # solo-prefill prompts pad to this granularity, so
#                     continuous admission compiles O(max_len/16) shapes


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (len,) int32
    max_new: int = 32
    temperature: float = 0.0      # 0 -> greedy
    priority: int = 0             # admission-control rank: LOWER sheds
    #                               first under backpressure (supervisor)
    tenant: str = ""              # multi-tenant routing key: names a
    #                               ServeConfig.tenants entry ("" rides
    #                               the first tenant; single-tenant
    #                               groups ignore it)
    t_submit: Optional[float] = dataclasses.field(default=None,
                                                  compare=False)
    #                               perf_counter at EventLoopGroup.submit
    #                               (the engine's entry if never submitted)

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32)


@dataclasses.dataclass
class Result:
    """A finished request and its timeline, all on ``time.perf_counter``:
    submission, the host-ready time of its first token, completion, and
    the ready time of each generated token (``token_times[0] ==
    t_first`` when it generated any)."""
    uid: int
    tokens: np.ndarray            # generated tokens (<= max_new)
    prompt_len: int
    steps: int
    t_submit: float = dataclasses.field(default=0.0, compare=False)
    t_first: float = dataclasses.field(default=0.0, compare=False)
    t_done: float = dataclasses.field(default=0.0, compare=False)
    token_times: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,)), compare=False)


@dataclasses.dataclass
class _Slot:
    """One in-flight request occupying a decode slot. Its tokens after
    the first are read at loop steps ``admitted_step`` on (the wave's
    ``ready`` times); ``t_first`` is set when admission sampled its
    first token from its own prefill."""
    req: Request
    admitted_step: int
    toks: list
    done: bool = False
    t_first: Optional[float] = None

    def token_times(self, ready: list) -> np.ndarray:
        lead = [] if self.t_first is None else [self.t_first]
        n = len(self.toks) - len(lead)
        return np.asarray(lead + ready[self.admitted_step:
                                       self.admitted_step + n])


class DecodeEngine:
    """Synchronous batched engine around prefill/decode_step.

    ``max_batch`` bounds the decode slots; ``max_len`` bounds prompt+gen
    length (the KV-cache allocation). With ``serve`` set, the steps are
    built by :func:`repro.serving.dispatch.make_serve_step` and every
    serving collective flows through ``serve.comm``'s backend wire;
    ``channel_indices`` is the owning event loop's channel affinity.
    """

    def __init__(self, cfg: ModelConfig, params: PyTree, *,
                 max_batch: int = 8, max_len: int = 256,
                 eos_id: Optional[int] = None, shard_fn=no_shard,
                 rng: Optional[jax.Array] = None,
                 serve: Optional[ServeConfig] = None, mesh=None,
                 channel_indices: Optional[tuple] = None,
                 poller: Optional[Poller] = None, loop_index: int = 0):
        self.cfg = cfg
        self.loop_index = loop_index      # the owning event loop (spans)
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.shard_fn = shard_fn
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.serve = serve
        self._recurrent = cfg.family in ("ssm", "hybrid")
        self.poller = poller or Poller(
            serve.poll if serve else "park",
            serve.spin_us * 1e-6 if serve else 50e-6)
        # chaos seam: the flush-boundary fault window. Called as
        # hook(engine, step) at every decode-step boundary (after the
        # poll, before queue admission); returned Requests join the run
        # queue and contend for freed slots — the admission-storm
        # injection point (serving/chaos.py). None = no-op.
        self.admission_hook = None
        # admission-control seam (serving/supervisor.py): called as
        # gate(engine, step, extra) with the hook-injected burst; returns
        # the requests actually admitted to the run queue. Distinct from
        # admission_hook so supervisor backpressure composes with chaos
        # storms instead of clobbering them. None = admit everything.
        self.admission_gate = None
        self.admit_prefills = 0   # batched-admission prefill calls (one
        #                           per flush boundary with freed slots,
        #                           NOT one per admitted request)

        if serve is not None:
            self.step = dispatch.make_serve_step(
                cfg, serve.comm, mesh, channel_indices=channel_indices,
                pod_axis=serve.pod_axis if serve.pods > 1 else None)
            self._prefill = self.step.prefill
            self._decode = self.step.decode
            self.n_shards = self.step.n_shards
        else:
            self.step = None
            self.n_shards = 1
            self._prefill = jax.jit(
                lambda p, b: api.prefill(p, b, cfg, shard_fn))
            self._decode = jax.jit(
                lambda p, c, b: api.decode_step(p, c, b, cfg, shard_fn))

    # -- batching ------------------------------------------------------

    def _buckets(self, reqs: Sequence[Request]) -> list:
        """Recurrent archs bucket by exact prompt length (no pads)."""
        groups = defaultdict(list)
        for r in reqs:
            groups[len(r.prompt)].append(r)
        out = []
        for _, rs in sorted(groups.items()):
            for i in range(0, len(rs), self.max_batch):
                out.append(rs[i:i + self.max_batch])
        return out

    # -- sampling ------------------------------------------------------

    def _sample(self, logits: jax.Array, temps: np.ndarray) -> jax.Array:
        self.rng, k = jax.random.split(self.rng)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = jnp.asarray(np.maximum(temps, 1e-6), jnp.float32)
        sampled = jax.random.categorical(k, logits / t[:, None], axis=-1)
        return jnp.where(jnp.asarray(temps) > 0.0,
                         sampled.astype(jnp.int32), greedy)

    # -- main entry ----------------------------------------------------

    def generate(self, reqs: Sequence[Request]) -> list:
        reqs = list(reqs)
        now = time.perf_counter()
        for r in reqs:
            if r.t_submit is None:
                r.t_submit = now
        results: list = []
        if self._recurrent:
            # equal-length buckets; no mid-flight admission (the
            # recurrence has no pad-exactness to admit against)
            for bucket in self._buckets(reqs):
                results.extend(self._run_wave(bucket, deque()))
        elif reqs:
            initial = reqs[: self.max_batch]
            pending = deque(reqs[self.max_batch:])   # the run queue
            results.extend(self._run_wave(initial, pending))
        results.sort(key=lambda r: r.uid)
        return results

    # -- batch assembly ------------------------------------------------

    def _prefill_batch(self, toks: np.ndarray, lens: np.ndarray) -> dict:
        b = toks.shape[0]
        batch = {"tokens": jnp.asarray(toks)}
        if not self._recurrent:
            batch["last_pos"] = jnp.asarray(np.maximum(lens - 1, 0))
        if self.cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (b, self.cfg.num_patches, self.cfg.d_model),
                jnp.dtype(self.cfg.compute_dtype))
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (b, self.cfg.num_frames, self.cfg.d_model),
                jnp.dtype(self.cfg.compute_dtype))
        return batch

    # -- the slot loop -------------------------------------------------

    def _run_wave(self, initial: list, pending: deque) -> list:
        b = len(initial)
        R = self.n_shards
        b_pad = max(R, -(-b // R) * R)    # rows padded to the ring size
        with obs_trace.span("wave_setup", f"wave_b{b}", batch=b):
            lens = np.zeros((b_pad,), np.int32)
            for i, r in enumerate(initial):
                lens[i] = len(r.prompt)
            pad_to = int(lens.max())
            assert pad_to + max(r.max_new for r in initial) \
                <= self.max_len, "prompt + max_new exceeds engine max_len"
            toks = np.zeros((b_pad, pad_to), np.int32)
            for i, r in enumerate(initial):
                toks[i, : lens[i]] = r.prompt

        with obs_trace.span("prefill", f"wave_b{b}", batch=b,
                            pad_to=pad_to):
            logits, cache = self._prefill(self.params,
                                          self._prefill_batch(toks, lens))
            self.poller.wait(logits)
        with obs_trace.span("grow_cache", f"wave_b{b}", batch=b):
            cache = api.grow_cache(self.cfg, cache, self.max_len)

        slots: list = [_Slot(r, 0, []) for r in initial] \
            + [None] * (b_pad - b)
        temps = np.zeros((b_pad,), np.float32)
        for i, r in enumerate(initial):
            temps[i] = r.temperature
        pos = jnp.asarray(lens)           # next write slot per request
        tok = self._sample(logits, temps)
        steps = 0
        results: list = []
        ready: list = []                  # host-ready time of each loop
        #                                   step's tokens (one clock read)
        span = None                       # the open decode or host_step
        try:
            while True:
                # flush boundary: the staged emission's channel flushes
                # for this step are complete once the sampled tokens are
                # ready. The decode span ends here and the host step
                # (token read, slot bookkeeping, admission) begins.
                self.poller.wait(tok)
                now = time.perf_counter()
                ready.append(now)
                obs_trace.end(span)
                span = obs_trace.begin("host_step", f"step{steps}",
                                       loop=self.loop_index, step=steps)
                tok_np = np.asarray(tok)
                for i, s in enumerate(slots):
                    if s is None:
                        continue
                    if s.req.max_new > 0:  # max_new=0: prefill-only
                        s.toks.append(int(tok_np[i]))
                        if self.eos_id is not None \
                                and s.toks[-1] == self.eos_id:
                            s.done = True
                    if len(s.toks) >= s.req.max_new:
                        s.done = True
                    if s.done:
                        times = s.token_times(ready)
                        results.append(_result(
                            s.req, s.toks, steps + 1 - s.admitted_step,
                            float(times[0]) if len(times) else now, now,
                            times))
                        slots[i] = None
                steps += 1
                # the flush-boundary fault window: storm requests
                # injected here enter the run queue like any client's and
                # are admitted (or queued) by the very same admission
                # path below — per-row exactness keeps the residents'
                # tokens bit-identical. The gate sees the burst AFTER the
                # hook, so supervisor backpressure composes with storms.
                if not self._recurrent and (
                        self.admission_hook is not None
                        or self.admission_gate is not None):
                    extra = list(self.admission_hook(self, steps) or []) \
                        if self.admission_hook is not None else []
                    if self.admission_gate is not None:
                        extra = self.admission_gate(self, steps, extra)
                    for r in extra:
                        if r.t_submit is None:
                            r.t_submit = now
                    pending.extend(extra)
                # continuous batching: admit from the run queue into
                # freed slots, at this flush boundary — ONE batched
                # prefill over every freed slot (solo == batched
                # bit-for-bit; exactness is per-row). Only the first
                # max_batch slots are admission-eligible — ring-padding
                # rows beyond the configured bound carry no requests.
                if pending and not self._recurrent:
                    tok, cache, pos = self._admit_ready(
                        pending, cache, pos, temps, tok, steps, slots,
                        results)
                if not any(s is not None for s in slots) and not pending:
                    break
                active = np.array([s is not None for s in slots])
                obs_trace.end(span)
                # the decode span runs from this enqueue to the moment
                # its sampled tokens are ready (the wait above)
                span = obs_trace.begin(
                    "decode", f"step{steps}", loop=self.loop_index,
                    step=steps, active=int(active.sum())) \
                    if obs_trace.enabled() else None
                logits, cache = self._decode(self.params, cache,
                                             {"token": tok, "pos": pos})
                tok = self._sample(logits, temps)
                pos = jnp.where(jnp.asarray(active), pos + 1, pos)
        finally:
            obs_trace.end(span)
        return results

    def _admit_ready(self, pending: deque, cache: PyTree, pos: jax.Array,
                     temps: np.ndarray, tok: jax.Array, steps: int,
                     slots: list, results: list):
        """Admit from the run queue into EVERY freed slot at this flush
        boundary with one batched prefill per round (the ROADMAP's
        batched admission — replacing the one-prefill-per-slot solo
        path). Loops because a request finishing AT admission
        (eos / max_new<=1) leaves its slot free for the next queued
        request within the same boundary; each round pops at least one
        request, so it terminates."""
        while pending:
            free = [i for i in range(min(len(slots), self.max_batch))
                    if slots[i] is None]
            if not free:
                break
            take = min(len(free), len(pending))
            batch = [pending.popleft() for _ in range(take)]
            if obs_trace.enabled():
                with obs_trace.span("admission", f"admit{take}", n=take,
                                    step=steps):
                    tok, cache, pos = self._admit_batch(
                        free[:take], batch, cache, pos, temps, tok,
                        steps, slots, results)
            else:
                tok, cache, pos = self._admit_batch(
                    free[:take], batch, cache, pos, temps, tok, steps,
                    slots, results)
        return tok, cache, pos

    def _admit_batch(self, free: list, reqs: list, cache: PyTree,
                     pos: jax.Array, temps: np.ndarray, tok: jax.Array,
                     steps: int, slots: list, results: list):
        """Admit ``reqs[j]`` into freed slot ``free[j]`` with ONE prefill
        over the whole batch (rows padded to the ring size; prompts
        right-padded to the batch max — exactness is per-row, so this is
        bit-identical to solo admission). Each request's first token is
        sampled from its own prefill logits AND recorded immediately (it
        is the request's first generated token — the main loop's append
        phase has already run this step, and the next one records the
        token sampled AFTER it). A request done at its first token (eos,
        or max_new == 1) finishes here and leaves the slot free. Mutates
        ``temps`` / ``slots`` / ``results`` in place; returns the new
        (tok, cache, pos)."""
        R = self.n_shards
        k = len(reqs)
        rows = max(R, -(-k // R) * R)     # rows padded to the ring size
        for req in reqs:
            assert len(req.prompt) + req.max_new <= self.max_len, \
                "prompt + max_new exceeds engine max_len"
        # round for bounded recompiles, but never past the resident
        # cache's sequence capacity (max_len, or the rolling window) —
        # an over-rounded prefill cache could not fit the slot write
        limit = self.max_len
        if self.cfg.sliding_window:
            limit = min(limit, self.cfg.sliding_window)
        pmax = max(len(req.prompt) for req in reqs)
        pad_to = min(-(-pmax // ADMIT_PAD) * ADMIT_PAD, max(pmax, limit))
        toks = np.zeros((rows, pad_to), np.int32)
        lens = np.zeros((rows,), np.int32)
        rtemps = np.zeros((rows,), np.float32)
        for row, req in enumerate(reqs):
            plen = len(req.prompt)
            toks[row, :plen] = req.prompt
            lens[row] = plen
            rtemps[row] = req.temperature
        logits1, cache1 = self._prefill(self.params,
                                        self._prefill_batch(toks, lens))
        self.poller.wait(logits1)
        self.admit_prefills += 1
        t_arr = self._sample(logits1, rtemps)
        t_np = np.asarray(t_arr)
        now = time.perf_counter()         # the first tokens are ready
        live_rows: list = []
        live_slots: list = []
        for row, (i, req) in enumerate(zip(free, reqs)):
            t0 = int(t_np[row])
            if req.max_new <= 0:          # prefill-only: zero tokens
                results.append(_result(req, [], 0, now, now, np.zeros(0)))
                continue
            if (self.eos_id is not None and t0 == self.eos_id) \
                    or req.max_new == 1:  # finished at its first token
                results.append(_result(req, [t0], 1, now, now,
                                       np.asarray([now])))
                continue
            live_rows.append(row)
            live_slots.append(i)
            temps[i] = req.temperature
            slots[i] = _Slot(req, steps, [t0], t_first=now)
        if live_rows:
            with obs_trace.span("grow_cache", f"admit{k}", batch=k):
                cache1 = api.grow_cache(self.cfg, cache1, self.max_len)
            rsel = np.asarray(live_rows)
            ssel = np.asarray(live_slots)
            # attention caches carry batch at axis 1 (L, B, S, KV, Dh)
            cache = jax.tree.map(lambda c, n: c.at[:, ssel].set(n[:, rsel]),
                                 cache, cache1)
            tok = tok.at[ssel].set(t_arr[rsel])
            pos = pos.at[ssel].set(jnp.asarray(lens[rsel]))
        return tok, cache, pos


def _result(req: Request, toks: list, steps: int, t_first: float,
            t_done: float, times: np.ndarray) -> Result:
    """A finished request's Result; with the recorder on, also its
    zero-length ``request`` event carrying the timeline."""
    t_sub = req.t_submit
    if obs_trace.enabled():
        obs_trace.complete("request", f"uid{req.uid}", t_done, t_done,
                           uid=req.uid, ttft_ms=(t_first - t_sub) * 1e3,
                           done_ms=(t_done - t_sub) * 1e3,
                           tokens=len(toks))
    return Result(uid=req.uid, tokens=np.asarray(toks, np.int64),
                  prompt_len=len(req.prompt), steps=steps, t_submit=t_sub,
                  t_first=t_first, t_done=t_done, token_times=times)


# ---------------------------------------------------------------------------
# Event-loop glue: one engine per loop, channel affinity baked in
# ---------------------------------------------------------------------------


def make_engine_group(cfg: ModelConfig, params: PyTree, serve: ServeConfig,
                      *, mesh=None, eos_id: Optional[int] = None,
                      seed: int = 0,
                      affinity: Optional[tuple] = None) -> EventLoopGroup:
    """The serving subsystem's front door: an
    :class:`~repro.serving.event_loop.EventLoopGroup` of
    ``serve.event_loops`` loops, each owning a disjoint contiguous run of
    the ``serve.comm.channels`` pool (``channel_affinity``) and driving
    its OWN :class:`DecodeEngine` whose serve step emits only on those
    channels. Requests submitted to the group are assigned round-robin;
    results merge by uid. GREEDY outputs are bit-identical for any
    ``event_loops`` (the affinity changes emission structure, never
    logits — conformance-tested); temperature>0 requests draw from each
    engine's own PRNG stream, so sampled tokens legitimately vary with
    the loop assignment.

    With ``serve.pods > 1`` the group is TOPOLOGY-AWARE: the default
    mesh becomes the two-level ``(pod_axis, "data")`` fabric
    (``launch/mesh.make_serve_mesh``), and — when ``comm.hierarchical``
    keeps pod-aware collectives on — the affinity pins the pool's
    leader lanes to the first ``serve.leader_loops`` loops while each
    remaining loop owns only local lanes whose peers share a pod
    (``channel_affinity`` topology form).

    ``affinity`` overrides the computed partition with an explicit one
    (validated disjoint + covering + nonempty per loop) — the elastic
    reshard path (``launch/elastic.reshard_affinity`` keeps migrations
    minimal, so the resharded partition is deliberately NOT what
    ``channel_affinity`` would recompute) and the supervisor's rebuilds
    both use it.

    MULTI-TENANT form: with ``serve.tenants`` set, the loops are carved
    into contiguous per-tenant ranges in declaration order (tenant 0
    owns loops ``0..e0-1``, tenant 1 the next ``e1``, …) so channel
    ownership stays disjoint per tenant, and ``cfg`` / ``params`` may
    EACH be either a single value (every tenant serves the same model)
    or a dict keyed by tenant name (heterogeneous families side by
    side — one group, one channel pool, different engines per range).
    The group then routes ``Request.tenant`` to the owning range with
    deterministic weighted-fair scheduling (``EventLoopGroup``
    docstring; docs/FAMILIES.md §Tenants and fairness)."""
    if serve.pods > 1 and mesh is None:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(serve.pods, serve.pod_axis)
    if affinity is not None:
        affinity = tuple(tuple(g) for g in affinity)
        owned = sorted(c for g in affinity for c in g)
        if len(affinity) != serve.event_loops \
                or owned != list(range(serve.comm.channels)) \
                or any(not g for g in affinity):
            raise ValueError(
                f"explicit affinity {affinity} must partition channels "
                f"0..{serve.comm.channels - 1} into {serve.event_loops} "
                "nonempty disjoint groups")
    elif serve.pods > 1 and serve.comm.hierarchical:
        affinity = channel_affinity(
            serve.comm.channels, serve.event_loops, n_pods=serve.pods,
            leaders=min(serve.comm.leader_channels,
                        serve.comm.channels - 1),
            leader_loops=serve.leader_loops)
    else:
        affinity = channel_affinity(serve.comm.channels, serve.event_loops)
    bindings = []
    loop_tenant = {}
    start = 0
    for t in serve.tenants:
        ix = tuple(range(start, start + t.event_loops))
        bindings.append((t.name, t.weight, ix))
        for i in ix:
            loop_tenant[i] = t.name
        start += t.event_loops

    names = {t.name for t in serve.tenants}

    def resolve(v, what, always_dict=False):
        # params is a pytree that is ITSELF a dict, so a dict is treated
        # as per-tenant only when its keys touch the tenant names
        per_tenant = isinstance(v, dict) and (
            always_dict or (names and set(v) & names))
        if not per_tenant:
            return (lambda _name: v)
        if not names:
            raise ValueError(
                f"{what} is a per-tenant dict but serve.tenants is empty: "
                "heterogeneous groups need named tenants to route by")
        if set(v) != names:
            raise ValueError(
                f"{what} keys {sorted(v)} must match the tenant names "
                f"{sorted(names)} exactly (one model binding per tenant)")
        return v.__getitem__

    cfg_of = resolve(cfg, "cfg", always_dict=True)
    params_of = resolve(params, "params")
    loops = []
    for i, chans in enumerate(affinity):
        name = loop_tenant.get(i, "")
        loop = EventLoop(i, channels=chans, poll=serve.poll,
                         spin_s=serve.spin_us * 1e-6)
        eng = DecodeEngine(cfg_of(name), params_of(name),
                           max_batch=serve.max_batch,
                           max_len=serve.max_len, eos_id=eos_id,
                           rng=jax.random.PRNGKey(seed + i), serve=serve,
                           mesh=mesh, channel_indices=chans,
                           poller=loop.poller, loop_index=i)
        loop.engine = eng
        loop.runner = lambda _loop, items, eng=eng: eng.generate(items)
        loops.append(loop)
    return EventLoopGroup(loops, tenants=bindings or None)
