"""Span tracing for the Observatory telemetry plane.

Nested host spans over the serving plane (event-loop drains, waves,
prefill, the decode step and the host work between steps, admission,
each finished request's timeline, supervisor heal windows), recorded into
a ring-buffered :class:`TraceRecorder` and exported as Chrome-trace /
Perfetto JSON (``launch/serve.py --trace-out``).

Design rules:

* **One span system, on the profiler's clock.** While a recorder is
  enabled every span also opens a ``jax.profiler.TraceAnnotation`` named
  ``repro.<kind>`` whose keyword arguments are the span's ``name`` and
  args: under ``jax.profiler.trace`` each span is a ``/host:CPU`` event
  with those args as stats, on the same clock as the device's ops.
  ``complete()`` (spans from explicit stamps) leaves a zero-length event
  at the call, carrying the span's length as ``dur_ms``.
* **Zero overhead when disabled.** ``enabled()`` is a ``None`` check,
  :func:`span` returns a shared ``nullcontext`` and :func:`begin` returns
  ``None`` without touching a clock or the profiler.
* **Observation only.** Nothing here feeds back into scheduling or
  numerics, so traced runs serve bit-identical tokens (tested). The
  device work has no spans: the model and the comm layer name it with
  ``jax.named_scope`` (``attn``, ``comm.flush.ch<i>`` ...), which lands in
  each HLO op's ``op_name`` and costs nothing at run time.
* **Thread safety.** Each thread keeps its own span stack; a span's
  ``parent`` is the kind of the span enclosing it on its thread. The ring
  and the tid table are lock-protected.

Span kinds (``KINDS``, docs/OBSERVABILITY.md): ``drain`` (event_loop.py),
``submit`` / ``join`` (the group's side of ``EventLoopGroup.submit`` and
``run``), ``wave_setup`` / ``prefill`` / ``grow_cache`` / ``decode`` /
``host_step`` / ``admission`` / ``request`` (engine.py), ``heal``
(supervisor.py).
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

# The instrumented span kinds (the recorder accepts any string; this is
# the documented taxonomy the smoke assertions key on).
KINDS = ("drain", "submit", "join", "wave_setup", "prefill", "grow_cache",
         "decode", "host_step", "admission", "request", "heal")


@dataclass
class Span:
    """One closed span. Times are seconds relative to the recorder's
    epoch (``perf_counter`` at construction); ``depth`` is the nesting
    depth at close time on the recording thread (0 = top level) and
    ``parent`` the kind of the span that enclosed it ("" at top level)."""
    kind: str
    name: str
    t0: float
    dur: float
    tid: int
    depth: int
    args: dict = field(default_factory=dict)
    parent: str = ""

    @property
    def t1(self) -> float:
        return self.t0 + self.dur


class TraceRecorder:
    """Ring-buffered span recorder with per-thread nesting stacks.

    ``capacity`` bounds the ring: the oldest span is evicted and counted
    in ``dropped``. ``forced_closes`` counts non-LIFO closes (an ``end``
    whose token was not on top: the spans above it are force-closed so the
    trace stays an interval forest); a well-formed run keeps it at 0."""

    def __init__(self, capacity: int = 65536):
        self.capacity = int(capacity)
        self.spans: deque = deque(maxlen=self.capacity)
        self.dropped = 0
        self.forced_closes = 0
        self._epoch = time.perf_counter()
        self._local = threading.local()
        self._lock = threading.Lock()
        self._tids: Dict[int, int] = {}
        self._stacks: Dict[int, list] = {}   # tid -> live stack (open_spans)

    # -- clocks / identity ---------------------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self._epoch

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            if ident not in self._tids:
                self._tids[ident] = len(self._tids)
            return self._tids[ident]

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
            tid = self._tid()            # before the lock: _tid locks too
            with self._lock:
                self._stacks[tid] = st
        return st

    # -- the span API ---------------------------------------------------

    def begin(self, kind: str, name: str = "", **args) -> list:
        """Open a span and its profiler annotation; returns an opaque
        token for :meth:`end`."""
        st = self._stack()
        ann = TraceAnnotation("repro." + kind, name=name, **args)
        ann.__enter__()
        tok = [kind, name, self._now(), args, st[-1][0] if st else "", ann]
        st.append(tok)
        return tok

    def end(self, token: Optional[list] = None, **extra) -> Optional[Span]:
        """Close the span ``token`` (or the top of this thread's stack).
        A non-LIFO token force-closes the spans above it; a token not on
        this thread's stack is counted and ignored (both in
        ``forced_closes``): ends never raise on the serving path."""
        st = self._stack()
        if token is not None and not any(t is token for t in st):
            self.forced_closes += 1
            return None
        out = None
        while st:
            top = st.pop()
            if token is None or top is token:
                out = self._emit(top, extra)
                break
            self.forced_closes += 1          # non-LIFO close
            self._emit(top, {})
        return out

    @contextlib.contextmanager
    def span(self, kind: str, name: str = "", **args):
        tok = self.begin(kind, name, **args)
        try:
            yield tok
        finally:
            self.end(tok)

    def complete(self, kind: str, name: str, t0_s: float, t1_s: float,
                 **args) -> Span:
        """Record a span from explicit ``perf_counter`` stamps, bypassing
        the nesting stacks (heal windows, request timelines)."""
        dur = max(0.0, t1_s - t0_s)
        with TraceAnnotation("repro." + kind, name=name, dur_ms=dur * 1e3,
                             **args):
            pass
        st = self._stack()
        sp = Span(kind, name, t0_s - self._epoch, dur, self._tid(),
                  depth=len(st), args=dict(args),
                  parent=st[-1][0] if st else "")
        self._append(sp)
        return sp

    def _emit(self, tok: list, extra: dict) -> Span:
        kind, name, t0, args, parent, ann = tok
        if extra:
            args = {**args, **extra}
            ann.set_metadata(**extra)
        ann.__exit__(None, None, None)
        sp = Span(kind, name, t0, self._now() - t0, self._tid(),
                  depth=len(self._stack()), args=args, parent=parent)
        self._append(sp)
        return sp

    def _append(self, sp: Span) -> None:
        with self._lock:
            if len(self.spans) == self.capacity:
                self.dropped += 1            # ring eviction, counted
            self.spans.append(sp)

    # -- introspection --------------------------------------------------

    def open_spans(self) -> list:
        """Every thread's still-open ``(kind, name)`` pairs — the
        well-formedness probe (a clean run returns [])."""
        with self._lock:
            stacks = list(self._stacks.values())
        return [(t[0], t[1]) for st in stacks for t in st]

    def kinds(self) -> list:
        with self._lock:
            return sorted({s.kind for s in self.spans})

    def spans_of(self, kind: str) -> list:
        with self._lock:
            return [s for s in self.spans if s.kind == kind]

    # -- export ---------------------------------------------------------

    def to_chrome(self) -> dict:
        """Chrome-trace JSON object (the ``traceEvents`` array of
        complete ``"ph": "X"`` events, microsecond timestamps) —
        loadable by chrome://tracing and Perfetto."""
        evs: List[dict] = []
        with self._lock:
            spans = list(self.spans)
        for s in spans:
            evs.append({"name": s.name or s.kind, "cat": s.kind,
                        "ph": "X", "ts": round(s.t0 * 1e6, 3),
                        "dur": round(s.dur * 1e6, 3), "pid": 0,
                        "tid": s.tid, "args": dict(s.args)})
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"dropped": self.dropped,
                              "forced_closes": self.forced_closes,
                              "open_spans": len(self.open_spans())}}

    def write(self, path: str) -> dict:
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        return doc


def well_formed(rec: TraceRecorder) -> tuple:
    """``(ok, problems)``: every opened span closed, no forced closes,
    and per thread a proper interval forest (children inside their
    parents; the contract the tests and the obs-smoke CI job assert)."""
    problems: list = []
    open_ = rec.open_spans()
    if open_:
        problems.append(f"{len(open_)} unclosed spans: {open_[:8]}")
    if rec.forced_closes:
        problems.append(f"{rec.forced_closes} forced (non-LIFO) closes")
    eps = 1e-9
    by_tid: Dict[int, list] = {}
    for s in rec.spans:
        by_tid.setdefault(s.tid, []).append(s)
    for tid, spans in by_tid.items():
        ends: list = []                      # stack of enclosing t1s
        for s in sorted(spans, key=lambda s: (s.t0, -s.dur)):
            while ends and ends[-1] <= s.t0 + eps:
                ends.pop()
            if ends and s.t1 > ends[-1] + eps:
                problems.append(
                    f"tid {tid}: span {s.kind}:{s.name} "
                    f"[{s.t0:.6f},{s.t1:.6f}] straddles its parent "
                    f"(ends {ends[-1]:.6f})")
            ends.append(s.t1)
    return (not problems, problems)


def containing(rec: TraceRecorder, inner: Span, kind: str) -> Optional[Span]:
    """The tightest span of ``kind`` (same thread) whose interval
    contains ``inner`` — nesting queries for tests ("every admission
    sits inside a host step")."""
    eps = 1e-9
    best = None
    for s in rec.spans:
        if s.kind != kind or s.tid != inner.tid or s is inner:
            continue
        if s.t0 <= inner.t0 + eps and inner.t1 <= s.t1 + eps:
            if best is None or s.dur < best.dur:
                best = s
    return best


# ---------------------------------------------------------------------------
# The module-level gate (obs.enabled()): span()/begin()/end()/complete()
# no-op on the disabled path without touching a clock.
# ---------------------------------------------------------------------------

_RECORDER: Optional[TraceRecorder] = None
_NULL = contextlib.nullcontext()             # reusable + reentrant


def enabled() -> bool:
    return _RECORDER is not None


def enable(capacity: int = 65536) -> TraceRecorder:
    """Install a fresh recorder (replacing any active one)."""
    global _RECORDER
    _RECORDER = TraceRecorder(capacity)
    return _RECORDER


def disable() -> Optional[TraceRecorder]:
    """Remove the active recorder and return it (for export)."""
    global _RECORDER
    rec, _RECORDER = _RECORDER, None
    return rec


def recorder() -> Optional[TraceRecorder]:
    return _RECORDER


def span(kind: str, name: str = "", **args):
    """Context manager: a recorded span when enabled, a shared
    ``nullcontext`` otherwise."""
    rec = _RECORDER
    if rec is None:
        return _NULL
    return rec.span(kind, name, **args)


def begin(kind: str, name: str = "", **args):
    """Token-style open (for spans that straddle loop iterations, such as
    the engine's decode step); None when disabled."""
    rec = _RECORDER
    return None if rec is None else rec.begin(kind, name, **args)


def end(token, **extra) -> None:
    rec = _RECORDER
    if rec is not None and token is not None:
        rec.end(token, **extra)


def complete(kind: str, name: str, t0_s: float, t1_s: float, **args) -> None:
    rec = _RECORDER
    if rec is not None:
        rec.complete(kind, name, t0_s, t1_s, **args)


@contextlib.contextmanager
def capture(capacity: int = 65536):
    """Scoped enable/disable (tests): yields the recorder, restores the
    previously-active one on exit."""
    global _RECORDER
    prev = _RECORDER
    rec = enable(capacity)
    try:
        yield rec
    finally:
        _RECORDER = prev
