"""Span tracer: the flight recorder's event source, and the per-request
span summary every verdict carries.

A thread-safe tracer with a context-manager API over monotonic clocks::

    from jepsen_tpu.obs import trace
    with trace.span("dispatch", site="host-fixpoint", cap=4096) as sp:
        ...
        sp.note(outcome="ok", passes=7)

Two things make a span real; otherwise :func:`span` returns one shared
:data:`NULL_SPAN` object (no span object, no event, no buffer touch),
so untraced code outside a request pays a context-variable read and an
``os.environ`` lookup:

- **A request.** :func:`request` opens one per ``lin.analysis`` call:
  an integer id and a root span named ``analysis``, held in a context
  variable, so threads started with ``contextvars.copy_context().run``
  report into it too. Each span closed inside it adds its count, its
  duration and its self time (duration less what its child spans
  cover) to the request's summary, and :func:`count` adds to its
  counters; :meth:`Request.record` is the ``spans``/``counts`` pair the
  verdict carries. Each in-request span is also a
  ``jax.profiler.TraceAnnotation`` named ``jepsen.<span>`` with the
  request id as metadata — on the profiler's clock, beside the
  device's ops, in a profiled run (jax is imported lazily, and only
  once the program has imported it).
- **The JSONL recorder** (``JEPSEN_TPU_TRACE=1``). Completed spans
  land in a bounded in-memory buffer (``JEPSEN_TPU_TRACE_BUF``
  events) that SPILLS to a JSONL file (``JEPSEN_TPU_TRACE_FILE``,
  default ``<repo>/.jax_cache/trace.jsonl``; ``0`` disables the file)
  instead of dropping — a killed run keeps everything already
  spilled, and ``atexit`` flushes the tail. One process per file: the
  first write of a process truncates it, so ``cli.py trace report``
  reads the most recent run.

Event shape (one JSON object per line)::

    {"name": ..., "ph": "X"|"i", "ts": <monotonic s>, "dur": <s>,
     "pid": ..., "tid": ..., "depth": <span nesting>,
     "req": <request id or null>, "parent": <enclosing span's name or
     null>, "args": {...}}

Timestamps are ``time.monotonic()`` seconds (relative, clock-nemesis
immune); :func:`jepsen_tpu.obs.report.to_chrome` converts to the
microsecond trace-event format Perfetto loads.

The tracer observes — it never routes, retries, or alters engine
behaviour.
"""

from __future__ import annotations

import atexit
import contextvars
import itertools
import json
import os
import sys
import threading
import time

from jepsen_tpu import util


def enabled() -> bool:
    """Master switch: ``JEPSEN_TPU_TRACE=1``. Re-read per call (the
    env-knob convention, doc/env.md) — one dict lookup on the disabled
    path."""
    return os.environ.get("JEPSEN_TPU_TRACE", "") not in ("", "0")


def trace_file() -> str | None:
    """The JSONL spill path; ``JEPSEN_TPU_TRACE_FILE=0`` keeps the
    trace purely in-memory (tests)."""
    env = os.environ.get("JEPSEN_TPU_TRACE_FILE", "")
    if env == "0":
        return None
    if env:
        return env
    return os.path.join(util.cache_dir(), "trace.jsonl")


def buf_cap() -> int:
    return util.env_int("JEPSEN_TPU_TRACE_BUF", 65536)


def max_bytes() -> int:
    """Spill-file rotation threshold (``JEPSEN_TPU_TRACE_MAX_MB``,
    default 256 MB; ``0`` = unlimited): looped probes under
    ``JEPSEN_TPU_TRACE=1`` must not grow the spill unbounded. Past it
    the live file rotates to ``<path>.1`` (one generation kept) and
    the spill continues fresh — ``trace report`` keeps reading the
    live path, which holds the newest events, and :func:`rotations`
    lets producers note the rotation in their perf-ledger record."""
    try:
        mb = util.env_float("JEPSEN_TPU_TRACE_MAX_MB", 256.0)
    except ValueError:
        # A malformed knob must not escape _flush_locked mid-dispatch
        # (run_guarded would read it as a device fault): tracing must
        # never take a run down — fall back to the default cap.
        mb = 256.0
    return int(mb * 1024 * 1024) if mb > 0 else 0


# Spill well before the ring cap so a configured file loses nothing;
# without a file the buffer is a true ring (oldest events drop).
_SPILL_BATCH = 4096
# Batch spills keep the newest events in memory so a tail_note()
# landing just after the boundary still reaches the file copy (the
# final flush writes everything).
_SPILL_KEEP = 64

_lock = threading.Lock()
_buf: list[dict] = []
_spilled = 0
_rotations = 0
_file_started = False
_file_dead = False
_atexit_on = False
_tls = threading.local()
# The innermost open real span of this context (thread, or a copy of a
# context run in another thread); its ``req`` is the open request.
_current: contextvars.ContextVar = contextvars.ContextVar(
    "jepsen_tpu_span", default=None)
_request_ids = itertools.count(1)
_annotation_cls = None


class _NullSpan:
    """The disabled-path singleton: enter/exit/note are no-ops."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **kw):
        pass


NULL_SPAN = _NullSpan()


def _annotation(name: str, req: int):
    """A ``jax.profiler.TraceAnnotation`` for an in-request span, or
    None while the program has not imported jax (``obs/`` stays
    jax-free at import time). With no profiler session it costs one
    native check."""
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls(f"jepsen.{name}", req=req)


class Span:
    """One traced interval. Use via :func:`span` as a context manager;
    ``note(**kw)`` merges attributes into the event's ``args`` (e.g.
    the outcome, pass counts). An exception exiting the span stamps
    ``outcome="error:<Type>"`` unless the site noted one already."""

    __slots__ = ("name", "meta", "req", "parent", "depth", "child_s",
                 "_record", "_t0", "_token", "_ann")

    def __init__(self, name: str, meta: dict, req, parent, record: bool):
        self.name = name
        self.meta = meta
        self.req = req
        self.parent = parent
        self.depth = 0 if parent is None else parent.depth + 1
        self.child_s = 0.0
        self._record = record
        self._t0 = 0.0
        self._token = None
        self._ann = None

    def __enter__(self):
        if self.req is not None:
            self._ann = _annotation(self.name, self.req.id)
            if self._ann is not None:
                self._ann.__enter__()
        self._token = _current.set(self)
        self._t0 = time.perf_counter()
        return self

    def note(self, **kw):
        self.meta.update(kw)

    def __exit__(self, et, ev, tb):
        dur = time.perf_counter() - self._t0
        _current.reset(self._token)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        req = self.req
        if req is not None:
            with req.lock:
                if self.parent is not None:
                    self.parent.child_s += dur
                req.add(self.name, dur, dur - self.child_s)
        if self._record:
            if et is not None and "outcome" not in self.meta:
                self.meta["outcome"] = f"error:{et.__name__}"
            _record({"name": self.name, "ph": "X",
                     "ts": time.monotonic() - dur, "dur": dur,
                     "pid": os.getpid(), "tid": threading.get_ident(),
                     "depth": self.depth,
                     "req": None if req is None else req.id,
                     "parent": None if self.parent is None
                     else self.parent.name,
                     "args": self.meta})
        return False


def span(name: str, **meta):
    """A new :class:`Span`: inside a request, or with the JSONL
    recorder on; else :data:`NULL_SPAN`."""
    cur = _current.get()
    req = None if cur is None else cur.req
    rec = enabled()
    if req is None and not rec:
        return NULL_SPAN
    return Span(name, meta, req, cur, rec)


class Request:
    """One checker request's record: ``spans`` maps each span name to
    ``{"n", "s", "self_s"}`` (count, seconds, self seconds) and
    ``counts`` each counter to its total. Open with :func:`request`;
    read :meth:`record` once it has closed."""

    __slots__ = ("id", "spans", "counts", "lock", "_root", "_compiles")

    def __init__(self):
        self.id = next(_request_ids)
        self.spans: dict = {}
        self.counts: dict = {}
        self.lock = threading.Lock()
        self._root = None
        self._compiles = None

    def add(self, name: str, dur: float, self_s: float) -> None:
        # Children on parallel threads (the competition racers) may
        # overlap their parent: its self time never reads below zero.
        self_s = max(self_s, 0.0)
        e = self.spans.get(name)
        if e is None:
            self.spans[name] = {"n": 1, "s": dur, "self_s": self_s}
        else:
            e["n"] += 1
            e["s"] += dur
            e["self_s"] += self_s

    def count(self, name: str, n: int) -> None:
        with self.lock:
            self.counts[name] = self.counts.get(name, 0) + n

    def __enter__(self):
        self._compiles = util.compile_counts()
        self._root = Span("analysis", {}, self, _current.get(), enabled())
        self._root.__enter__()
        return self

    def __exit__(self, et, ev, tb):
        self._root.__exit__(et, ev, tb)
        c0, c1 = self._compiles, util.compile_counts()
        if c0 is not None and c1 is not None:
            self.count("compile_requests", c1[0] - c0[0])
            self.count("compiles", c1[1] - c0[1])
        return False

    def record(self) -> dict:
        """``{"spans": {...}, "counts": {...}}``: copies, for the
        verdict."""
        with self.lock:
            return {"spans": {k: dict(v) for k, v in self.spans.items()},
                    "counts": dict(self.counts)}


def request() -> Request:
    """A new request scope (a context manager): see the module
    docstring."""
    return Request()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the open request; nothing
    outside one."""
    cur = _current.get()
    if cur is not None and cur.req is not None:
        cur.req.count(name, int(n))


def tail_note(**kw) -> None:
    """Annotate the most recently COMPLETED event on this thread —
    how call sites attach after-the-fact data (frontier count, pass
    totals) to a span that ended inside a helper (supervise.call)."""
    if not enabled():
        return
    ev = getattr(_tls, "last", None)
    if ev is not None:
        ev["args"].update(kw)


def complete(name: str, t0: float, dur_s: float, **meta) -> None:
    """Retro-record a completed interval (``t0`` in ``time.monotonic``
    seconds) — for lifecycles that cross threads (the checker daemon's
    admit->finish request path) or are measured externally (XLA
    compiles)."""
    if not enabled():
        return
    _record({"name": name, "ph": "X", "ts": t0, "dur": dur_s,
             "pid": os.getpid(), "tid": threading.get_ident(),
             "depth": 0, **_where(), "args": meta})


def instant(name: str, **meta) -> None:
    """A point event (wasted escalation rung, wave trip, quarantine
    hit)."""
    if not enabled():
        return
    _record({"name": name, "ph": "i", "ts": time.monotonic(),
             "dur": 0.0, "pid": os.getpid(),
             "tid": threading.get_ident(), "depth": 0, **_where(),
             "args": meta})


def _where() -> dict:
    """``req`` and ``parent`` of an event recorded at this point."""
    cur = _current.get()
    if cur is None:
        return {"req": None, "parent": None}
    return {"req": None if cur.req is None else cur.req.id,
            "parent": cur.name}


def _record(ev: dict) -> None:
    global _atexit_on
    _tls.last = ev
    with _lock:
        _buf.append(ev)
        if not _atexit_on:
            _atexit_on = True
            atexit.register(flush)
        path = None if _file_dead else trace_file()
        if path is not None:
            if len(_buf) >= _SPILL_BATCH:
                _flush_locked(path, keep=_SPILL_KEEP)
        else:
            cap = buf_cap()
            if len(_buf) > cap:
                del _buf[:len(_buf) - cap]


def _flush_locked(path: str, keep: int = 0) -> None:
    global _file_started, _spilled
    n = len(_buf) - keep
    if n <= 0:
        return
    # Serialize BEFORE touching the file, per event and exception-safe:
    # a tail_note() from another thread can mutate an args dict mid-
    # dumps (RuntimeError), and any failure escaping here would surface
    # inside an engine dispatch where run_guarded reads it as a device
    # fault and quarantines a healthy shape. Tracing must never take a
    # run down — a still-unserializable event is dropped, not fatal.
    lines = []
    for ev in _buf[:n]:
        try:
            lines.append(json.dumps(ev, default=str))
        except Exception:  # noqa: BLE001 - concurrent args mutation
            try:
                ev = dict(ev, args=dict(ev.get("args") or {}))
                lines.append(json.dumps(ev, default=str))
            except Exception:  # noqa: BLE001
                pass
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # Spill hygiene (JEPSEN_TPU_TRACE_MAX_MB): a live file already
        # past the cap rotates to <path>.1 BEFORE this write (one
        # generation kept), so looped probes can't fill the disk and
        # the configured path always exists holding the NEWEST events
        # — `trace report` reads it unchanged. Best-effort: rotation
        # failure degrades to an uncapped file, never a lost run.
        cap_bytes = max_bytes()
        if cap_bytes and _file_started:
            try:
                if os.path.getsize(path) >= cap_bytes:
                    os.replace(path, path + ".1")
                    _file_started = False
                    global _rotations
                    _rotations += 1
            except OSError:
                pass
        mode = "a" if _file_started else "w"
        with open(path, mode) as fh:
            for ln in lines:
                fh.write(ln + "\n")
        _file_started = True
        _spilled += n
        del _buf[:n]
    except OSError:
        # Spill failure degrades PERMANENTLY to the in-memory ring
        # (reset() re-arms): without the latch every later _record
        # would re-serialize the whole >=_SPILL_BATCH backlog under
        # the lock — an O(n^2) tax inside the engine dispatch path.
        # Tracing must never take a run down.
        global _file_dead
        _file_dead = True
        cap = buf_cap()
        if len(_buf) > cap:
            del _buf[:len(_buf) - cap]


def flush(path: str | None = None) -> str | None:
    """Write buffered events to the JSONL file (atexit calls this);
    returns the path, or None when the file is disabled."""
    with _lock:
        p = path or trace_file()
        if p is not None:
            _flush_locked(p)
        return p


def events() -> list[dict]:
    """Snapshot of the in-memory buffer (NOT the spilled file — use
    :func:`jepsen_tpu.obs.report.load` for a finished run's file)."""
    with _lock:
        return list(_buf)


def spilled() -> int:
    """Events already written to the spill file this process."""
    return _spilled


def rotations() -> int:
    """Spill-file rotations this process (``JEPSEN_TPU_TRACE_MAX_MB``)
    — producers stamp it into their perf-ledger record so a truncated
    trace summary is attributable."""
    return _rotations


def reset() -> None:
    """Drop all in-memory state (tests; the next flush truncates the
    file again so a test's trace file holds only its own run)."""
    global _spilled, _rotations, _file_started, _file_dead
    with _lock:
        _buf.clear()
        _spilled = 0
        _rotations = 0
        _file_started = False
        _file_dead = False
    _tls.last = None


# XLA compiles as trace events: the compile meter (util) runs the hook
# after every true backend compile; enabled() gating lives in
# complete().
def _on_compile(t0: float, dur_s: float) -> None:
    complete("xla-compile", t0, dur_s)


util.add_compile_hook(_on_compile)
