"""EventLoopGroup — the netty worker-group analogue (paper §IV).

The paper's microbenchmarks run an ``EventLoopGroup`` of worker threads:
each event loop OWNS a set of connections, polls their completions
(hadroNIO busy-polls the UCX worker instead of parking in epoll — the
single biggest latency lever, §IV-B), and drains a run queue of
in-flight requests. Ibdxnet (arXiv:1812.01963) shows the same design
scales concurrent Java/IB: dedicated per-thread connection ownership,
no shared mutable transport state between threads.

This module is that subsystem, transport-agnostic:

* :class:`Poller` — the completion-polling strategy (``busy`` spins on
  ``Array.is_ready``, ``park`` blocks — the epoll/selector fallback,
  ``adaptive`` spins for a bounded budget then parks), with counters so
  benchmarks can report how often each path was taken.
* :class:`EventLoop` — one loop: an index, the contiguous run of the
  global CommChannel pool it OWNS (the channel-affinity invariant: no
  two loops ever emit on the same channel), its own poller, and a run
  queue drained by a pluggable ``runner``.
* :class:`EventLoopGroup` — N loops; requests/connections are assigned
  round-robin (paper §IV-C assigns connections to selectors
  round-robin); ``run()`` drains every loop, one OS thread per loop
  when ``threads=True``.
* :func:`channel_affinity` — the bucket→channel grouping rule reused at
  the loop layer: ``selector.ready_groups``-style CONTIGUOUS runs of
  the channel pool, disjoint and covering, balanced to within one.

The engine glue (per-loop :class:`~repro.serving.engine.DecodeEngine`
with the loop's channel affinity baked into its serve step) lives in
``serving/engine.py`` (``make_engine_group``); the RTT microbenchmark
(``benchmarks/serving_rtt.py``) drives the same loops with raw
ping-pong connections.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax

from repro.core import selector
from repro.obs import trace as obs_trace
from repro.obs.metrics import RingLog

POLLS = ("busy", "park", "adaptive")


def channel_affinity(n_channels: int, n_loops: int, *, n_pods: int = 1,
                     leaders: int = 0, leader_loops: int = 1) -> tuple:
    """Partition the global channel pool ``0..n_channels-1`` into
    ``n_loops`` DISJOINT contiguous runs — each event loop's owned
    connections (``selector.ready_groups`` is exactly this grouping rule,
    applied to channels instead of buckets). Raises when a loop would own
    nothing: ownership is the invariant the subsystem is built on.

    The TOPOLOGY-AWARE form (``leaders > 0``) backs the two-level
    serving fabric: the pool's LAST ``leaders`` channels are the
    cross-pod leader lanes (``pipeline._leader_split`` carves the same
    tail) and are pinned to the first ``leader_loops`` loops — the
    designated leader loops, appended to their local runs. The remaining
    LOCAL lanes are partitioned with ``selector.pod_aligned_groups`` so
    a loop's owned locals never straddle a pod boundary: every loop's
    flushes complete on in-pod links without waiting on a cross-pod
    straggler, and only leader loops ever touch the scarce link.
    Ownership stays disjoint and covering in both forms."""
    if leaders <= 0:
        if n_loops > n_channels:
            raise ValueError(
                f"{n_loops} event loops over {n_channels} channels: every "
                "loop must own at least one channel (disjoint ownership); "
                "raise comm.channels or lower event_loops")
        return selector.ready_groups(n_channels, n_loops)
    n_local = n_channels - leaders
    if n_loops > n_local:
        raise ValueError(
            f"{n_loops} event loops over {n_local} local channels "
            f"({n_channels} minus {leaders} leader lanes): every loop "
            "must own at least one LOCAL channel (the in-pod stages are "
            "what loops emit); raise comm.channels or lower event_loops")
    if not 1 <= leader_loops <= n_loops:
        raise ValueError(
            f"leader_loops={leader_loops} must be in 1..{n_loops} "
            "(a leader lane needs an owning loop, and only existing "
            "loops can own one)")
    groups = [list(g) for g in selector.pod_aligned_groups(
        n_local, n_loops, min(n_pods, n_local))]
    for l, run in enumerate(selector.ready_groups(leaders,
                                                  min(leader_loops, leaders))):
        groups[l].extend(n_local + i for i in run)
    return tuple(tuple(g) for g in groups)


@dataclass
class PollStats:
    """How the loop waited: ``spins`` = readiness probes that came back
    not-ready, ``parks`` = blocking waits entered, ``waits`` = completed
    wait calls, ``stalls`` = parks FORCED by the fault seam (the chaos
    harness's over-parking loop — ``serving/chaos.py``), ``delays`` =
    waits the fault seam slowed down without forcing a park (a slow
    channel's completion arriving late). ``busy`` keeps parks at 0;
    ``park`` keeps spins at 0; a fault-free run keeps stalls and delays
    at 0 — both are pure health signals for the supervisor
    (``serving/supervisor.py``)."""
    spins: int = 0
    parks: int = 0
    waits: int = 0
    stalls: int = 0
    delays: int = 0

    def merge(self, other: "PollStats") -> "PollStats":
        return PollStats(self.spins + other.spins,
                         self.parks + other.parks,
                         self.waits + other.waits,
                         self.stalls + other.stalls,
                         self.delays + other.delays)


class Poller:
    """Completion polling for one event loop (hadroNIO §IV-B: busy-poll
    the worker vs. park in epoll; ``adaptive`` is the bounded spin).

    ``fault`` is the chaos seam (``serving/chaos.py``): when set, it is
    called once at the top of every :meth:`wait` with the poller itself
    and may sleep (a slow channel's completion arriving late) or return
    a verdict — ``"stall"`` forces an immediate park (the over-parking
    loop from Ibdxnet's failure catalogue, counted in ``stats.stalls``);
    ``"delay"`` reports that the hook slowed this wait down and proceeds
    normally, counted in ``stats.delays`` so the supervisor's health
    model can see slow channels without any wall-clock measurement.
    ``None`` (the default) is a zero-overhead no-op."""

    def __init__(self, poll: str = "busy", spin_s: float = 50e-6):
        assert poll in POLLS, poll
        self.poll = poll
        self.spin_s = spin_s
        self.stats = PollStats()
        self.fault: Optional[Callable[["Poller"], Optional[str]]] = None

    @staticmethod
    def _handles(tree: Any) -> list:
        return [l for l in jax.tree.leaves(tree) if hasattr(l, "is_ready")]

    @staticmethod
    def _ready(handles: list) -> bool:
        return all(h.is_ready() for h in handles)

    def _park(self, handles: list) -> None:
        self.stats.parks += 1
        for h in handles:
            h.block_until_ready()

    def wait(self, tree: Any) -> Any:
        """Wait for every jax array in ``tree`` per the strategy; returns
        ``tree`` so call sites can chain."""
        handles = self._handles(tree)
        self.stats.waits += 1
        if self.fault is not None:
            verdict = self.fault(self)
            if verdict == "stall":
                self.stats.stalls += 1  # forced over-park (chaos seam)
                self._park(handles)
                return tree
            if verdict == "delay":
                self.stats.delays += 1  # slowed wait; proceed normally
        if self.poll == "park" or (self.poll == "adaptive"
                                   and self.spin_s <= 0):
            # a zero spin budget IS park: straight to the epoll fallback,
            # exactly one park and zero probe burns
            self._park(handles)
            return tree
        deadline = (time.perf_counter() + self.spin_s
                    if self.poll == "adaptive" else None)
        while not self._ready(handles):
            self.stats.spins += 1
            if deadline is not None and time.perf_counter() >= deadline:
                self._park(handles)     # adaptive: bounded spin, then epoll
                break
        return tree


@dataclass(frozen=True)
class LoopFailure:
    """Structured record of one failed drain: WHICH loop died, WHAT
    killed it, and HOW MANY items were in flight (the in-flight batch
    plus anything still queued) — everything the supervisor needs to
    quarantine the loop and re-admit its requests. ``error`` is the
    ``repr`` of the exception (records must stay picklable/comparable);
    the live exception object stays on ``loop.error``."""
    loop_index: int
    error: str
    pending: int


class EventLoop:
    """One event loop: owned channels, a poller, and a run queue drained
    by ``runner(loop, items) -> list`` (the engine batches its items
    through the decode engine; the RTT bench ping-pongs them)."""

    def __init__(self, index: int, *, channels: Sequence[int] = (),
                 poll: str = "busy", spin_s: float = 50e-6,
                 runner: Optional[Callable] = None):
        self.index = index
        self.channels = tuple(channels)   # owned run of the global pool
        self.poller = Poller(poll, spin_s)
        self.runner = runner
        self.queue: deque = deque()       # run queue of in-flight items
        self.results: list = []
        self.error: Optional[BaseException] = None
        self.failed_items: list = []      # in-flight batch of a failed drain
        self.heartbeats = 0               # drained batches, ever — liveness
        self.restarts = 0
        self.lifetime_stats = PollStats()  # folded stats of RETIRED pollers
        #                                    (restart() accumulates here)
        # chaos seam: called with (loop, items) per drained batch, BEFORE
        # the runner — the injection point for queue-level faults and the
        # deterministic drain trace (serving/chaos.py)
        self.drain_hook: Optional[Callable] = None

    def submit(self, item: Any) -> None:
        self.queue.append(item)

    def drain(self) -> list:
        """Run everything queued through the runner (new submissions made
        while draining land in the queue and are picked up too). A
        runner failure is recorded in ``error`` (and re-raised) so a
        threaded group can propagate it instead of silently dropping the
        loop's requests; the in-flight batch is stashed in
        ``failed_items`` so a supervisor can re-admit it after a
        restart."""
        out: list = []
        self.error = None
        self.failed_items = []
        items: list = []
        try:
            while self.queue:
                items = list(self.queue)
                self.queue.clear()
                assert self.runner is not None, "event loop has no runner"
                if self.drain_hook is not None:
                    self.drain_hook(self, items)
                if obs_trace.enabled():
                    with obs_trace.span("drain", f"loop{self.index}",
                                        loop=self.index,
                                        items=len(items)):
                        out.extend(self.runner(self, items))
                else:
                    out.extend(self.runner(self, items))
                self.heartbeats += 1    # one beat per drained batch
        except BaseException as e:
            self.error = e
            self.failed_items = items
            raise
        finally:
            self.results = out
        return out

    def restart(self) -> Poller:
        """Quarantine-and-restart: replace the poller with a FRESH one
        (same strategy/spin budget — but no fault seam and zeroed
        counters, so a wedged or chaos-armed poller is genuinely
        cleared), forget the failure state, and re-point an attached
        engine at the new poller. The caller owns re-admitting
        ``failed_items``/queue contents; ``restarts`` counts how often
        this loop needed healing. The retiring poller's counters fold
        into ``lifetime_stats`` FIRST — a restart heals the loop, it
        must not erase its history (supervisor EWMAs and the group's
        merged ``poll_stats`` stay monotone across heals)."""
        self.lifetime_stats = self.lifetime_stats.merge(self.poller.stats)
        self.poller = Poller(self.poller.poll, self.poller.spin_s)
        self.error = None
        self.failed_items = []
        self.restarts += 1
        eng = getattr(self, "engine", None)
        if eng is not None:
            eng.poller = self.poller
        return self.poller

    def poll_stats(self) -> PollStats:
        """Lifetime poll counters: every retired poller's stats (folded
        at each :meth:`restart`) merged with the live poller's."""
        return self.lifetime_stats.merge(self.poller.stats)


class EventLoopGroup:
    """N event loops over one disjoint channel partition. ``submit``
    assigns items round-robin (paper §IV-C); ``run`` drains every loop —
    one OS thread per loop under ``threads=True`` (the multi-threaded
    benchmark topology), in-line otherwise (deterministic debugging).

    MULTI-TENANT form: ``tenants`` is a sequence of ``(name, weight,
    loop_indices)`` bindings that partition the loops (contiguous ranges
    built by ``engine.make_engine_group`` from ``ServeConfig.tenants``).
    ``submit`` then routes each item to ITS tenant's loops (round-robin
    within the tenant) and orders a mixed batch with a deterministic
    weighted-fair stride scheduler: the next dispatched item belongs to
    the tenant minimizing ``(dispatched[t] + 1) / weight[t]``, ties
    broken in declaration order — weights 2:1 yield the exact sequence
    A A B A A B. The cumulative per-tenant counters persist on
    ``fairness_counters`` and the per-item routing trace on
    ``dispatch_log`` (both are what the fairness tests and the
    family-matrix smoke assert on). Untagged items (``tenant`` empty or
    absent) ride the first tenant; an unknown tenant name raises."""

    def __init__(self, loops: Sequence[EventLoop],
                 tenants: Optional[Sequence] = None, *,
                 dispatch_log_capacity: int = 65536):
        assert loops, "an EventLoopGroup needs at least one loop"
        owned = [c for l in loops for c in l.channels]
        assert len(owned) == len(set(owned)), \
            f"channel ownership must be disjoint: {[l.channels for l in loops]}"
        self.loops = list(loops)
        self._rr = 0
        self.tenants = tuple(tenants) if tenants else ()
        self._torder = [t[0] for t in self.tenants]
        self._tweight = {n: w for n, w, _ in self.tenants}
        self._tloops = {n: tuple(ix) for n, _, ix in self.tenants}
        self._trr = {n: 0 for n in self._torder}
        self.fairness_counters = {n: 0 for n in self._torder}
        # tenant name per routed item — a bounded ring (long-running
        # serves must not grow memory; evictions count in .dropped and
        # surface through the obs registry as group.dispatch_log_dropped)
        self.dispatch_log = RingLog(dispatch_log_capacity)
        if self.tenants:
            allix = sorted(i for _, _, ix in self.tenants for i in ix)
            assert allix == list(range(self.n_loops)), \
                (f"tenant loop ranges must partition the group's "
                 f"{self.n_loops} loops: {self._tloops}")
        self.loop_failures = 0    # loops whose drain raised, across runs —
        #                           the failure-propagation counter the
        #                           chaos harness and the threaded-run
        #                           regression tests assert on
        self.failures: list = []  # structured LoopFailure records, in the
        #                           order failures were observed (appended
        #                           by BOTH threaded and inline drains) —
        #                           the supervisor's detect feed

    @property
    def n_loops(self) -> int:
        return len(self.loops)

    def submit(self, items: Any) -> None:
        """Round-robin connection→loop assignment; accepts one item or a
        sequence. With tenants, routes per tenant in weighted-fair
        stride order (see the class docstring)."""
        if not isinstance(items, (list, tuple)):
            items = [items]
        with obs_trace.span("submit", f"n{len(items)}", items=len(items)):
            self._submit(items)

    def _submit(self, items) -> None:
        now = time.perf_counter()
        for it in items:                  # the request timeline's start
            if hasattr(it, "t_submit"):
                it.t_submit = now
        if not self.tenants:
            for it in items:
                self.loops[self._rr % self.n_loops].submit(it)
                self._rr += 1
            return
        pending = {n: deque() for n in self._torder}
        for it in items:
            name = getattr(it, "tenant", "") or self._torder[0]
            if name not in pending:
                raise ValueError(
                    f"unknown tenant {name!r}: this group serves "
                    f"{self._torder} (Request.tenant must name one, or be "
                    "empty to ride the first tenant)")
            pending[name].append(it)
        remaining = sum(len(q) for q in pending.values())
        while remaining:
            name = min((n for n in self._torder if pending[n]),
                       key=lambda n: ((self.fairness_counters[n] + 1)
                                      / self._tweight[n]))
            it = pending[name].popleft()
            ix = self._tloops[name]
            self.loops[ix[self._trr[name] % len(ix)]].submit(it)
            self._trr[name] += 1
            self.fairness_counters[name] += 1
            self.dispatch_log.append(name)
            remaining -= 1

    def _record_failure(self, loop: EventLoop) -> None:
        self.loop_failures += 1
        self.failures.append(LoopFailure(
            loop.index, repr(loop.error),
            len(loop.failed_items) + len(loop.queue)))

    def run(self, *, threads: bool = True,
            raise_on_failure: bool = True) -> list:
        """Drain every loop; returns the concatenated results (loop
        order — callers sort by uid where ordering matters). A failure
        in ANY loop is recorded as a structured :class:`LoopFailure` in
        ``failures`` and — by default — propagates (after every thread
        has joined): a partial result set must never SILENTLY look like
        success. ``raise_on_failure=False`` is the supervisor's entry
        point: survivors' results are returned and the failure records
        plus each failed loop's ``failed_items`` carry everything needed
        to heal."""
        if threads and self.n_loops > 1:
            def guarded(loop):
                try:
                    loop.drain()
                except BaseException:
                    pass              # recorded in loop.error; raised below
            ts = [threading.Thread(target=guarded, args=(l,),
                                   name=f"event-loop-{l.index}")
                  for l in self.loops]
            for t in ts:
                t.start()
            with obs_trace.span("join", f"loops{self.n_loops}",
                                loops=self.n_loops):
                for t in ts:
                    t.join()
            failed = [l for l in self.loops if l.error is not None]
            for l in failed:
                self._record_failure(l)
            if failed and raise_on_failure:
                raise failed[0].error
        else:
            with obs_trace.span("join", f"loops{self.n_loops}",
                                loops=self.n_loops):
                for l in self.loops:
                    try:
                        l.drain()
                    except BaseException:
                        self._record_failure(l)
                        if raise_on_failure:
                            raise
        return [r for l in self.loops for r in l.results]

    def poll_stats(self) -> PollStats:
        st = PollStats()
        for l in self.loops:
            st = st.merge(l.poll_stats())   # lifetime: survives restarts
        return st
