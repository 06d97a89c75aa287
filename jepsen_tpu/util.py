"""Kitchen-sink utilities.

TPU-native re-design of the reference's ``jepsen/src/jepsen/util.clj`` (686
LoC): parallel map over unbounded workers (util.clj:44-50), majority
(util.clj:57-60), relative-time clock (util.clj:235-252), high-resolution
sleep (util.clj:254-260), timeout (util.clj:275-286), retry
(util.clj:288-327), compact integer-set rendering (util.clj:487-512),
latency extraction (util.clj:557-591) and nemesis intervals
(util.clj:593-610).
"""

from __future__ import annotations

import threading
import time as _time
from concurrent.futures import ThreadPoolExecutor
from fractions import Fraction
from typing import Any, Callable, Iterable, Sequence


def real_pmap(f: Callable, xs: Iterable) -> list:
    """Like map, but with one thread per element (reference util.clj:44-50:
    unbounded futures, used for SSH fan-out to all nodes at once)."""
    xs = list(xs)
    if not xs:
        return []
    with ThreadPoolExecutor(max_workers=len(xs)) as pool:
        return list(pool.map(f, xs))


def majority(n: int) -> int:
    """Given a cluster size, return the smallest majority: 1 for 0 or 1 nodes,
    2 for 3, 3 for 4 or 5 (reference util.clj:57-60)."""
    return max(1, n // 2 + 1)


def fraction(a: int, b: int):
    """a/b, but 1 when b is zero (reference util.clj `fraction`). Returns an
    exact :class:`fractions.Fraction` to mirror Clojure ratios."""
    if b == 0:
        return 1
    f = Fraction(a, b)
    return int(f) if f.denominator == 1 else f


def integer_interval_set_str(xs: Iterable[int]) -> str:
    """Render a set of integers as compact sorted intervals, e.g.
    ``#{1..3 5 7..9}`` (reference util.clj:487-512)."""
    xs = sorted(set(xs))
    parts: list[str] = []
    i = 0
    while i < len(xs):
        j = i
        while j + 1 < len(xs) and xs[j + 1] == xs[j] + 1:
            j += 1
        if j == i:
            parts.append(str(xs[i]))
        elif j == i + 1:
            parts.append(str(xs[i]))
            parts.append(str(xs[j]))
        else:
            parts.append(f"{xs[i]}..{xs[j]}")
        i = j + 1
    return "#{" + " ".join(parts) + "}"


# ---------------------------------------------------------------------------
# Relative time (reference util.clj:235-252). All op :time stamps are
# nanoseconds relative to an anchor established once per test run, so clock
# nemeses that scramble the wall clock cannot corrupt the history's
# timestamps (SURVEY.md §5 last bullet of fault-injection).
# ---------------------------------------------------------------------------

_relative_time_origin: float | None = None
_relative_time_lock = threading.Lock()


class relative_time_context:
    """Context manager anchoring the relative-time clock at entry
    (reference ``with-relative-time``, util.clj:243-247)."""

    def __enter__(self):
        global _relative_time_origin
        with _relative_time_lock:
            _relative_time_origin = _time.monotonic()
        return self

    def __exit__(self, *exc):
        return False


def relative_time_nanos() -> int:
    """Nanoseconds since the relative-time origin (util.clj:249-252). If no
    origin was anchored, anchors one now."""
    global _relative_time_origin
    if _relative_time_origin is None:
        with _relative_time_lock:
            if _relative_time_origin is None:
                _relative_time_origin = _time.monotonic()
    return int((_time.monotonic() - _relative_time_origin) * 1e9)


def sleep_nanos(ns: float) -> None:
    """Sleep for a number of nanoseconds (reference's high-res `sleep`,
    util.clj:254-260 — ops granularity is often sub-millisecond)."""
    if ns > 0:
        _time.sleep(ns / 1e9)


class TimeoutError_(Exception):
    pass


def timeout(seconds: float, f: Callable[[], Any], on_timeout: Any = TimeoutError_):
    """Run f in a worker thread; if it exceeds the deadline return
    ``on_timeout`` (or raise if it is an exception class). The worker is
    abandoned, mirroring the reference's interrupt-based `timeout`
    (util.clj:275-286) as closely as Python threading allows."""
    result: list = []
    err: list = []

    def run():
        try:
            result.append(f())
        except BaseException as e:  # noqa: BLE001 - report through the channel
            err.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    if t.is_alive():
        if isinstance(on_timeout, type) and issubclass(on_timeout, BaseException):
            raise on_timeout(f"timed out after {seconds}s")
        return on_timeout
    if err:
        raise err[0]
    return result[0]


def with_retry(f: Callable[[], Any], retries: int = 5, backoff: float = 0.2,
               exceptions: tuple = (Exception,)):
    """Call f, retrying on failure with linear backoff (reference
    `with-retry`/`retry`, util.clj:288-327)."""
    attempt = 0
    while True:
        try:
            return f()
        except exceptions:
            attempt += 1
            if attempt > retries:
                raise
            _time.sleep(backoff * attempt)


def longest_common_prefix(seqs: Sequence[Sequence]) -> list:
    """Longest prefix shared by all sequences (reference util.clj:612-625)."""
    if not seqs:
        return []
    out = []
    for vals in zip(*seqs):
        if all(v == vals[0] for v in vals[1:]):
            out.append(vals[0])
        else:
            break
    return out


def history_latencies(history) -> list:
    """Annotate invoke ops with :latency (ns between invoke and completion)
    and completion type, like reference util.clj:557-591. Returns a list of
    ``(invoke_op, latency_ns_or_None, completion_type_or_None)``."""
    pending: dict[Any, Any] = {}
    out = []
    for op in history:
        if op.type == "invoke":
            pending[op.process] = op
        elif op.process in pending:
            inv = pending.pop(op.process)
            out.append((inv, (op.time or 0) - (inv.time or 0), op.type))
    for inv in pending.values():
        out.append((inv, None, None))
    return out


def nemesis_intervals(history) -> list[tuple]:
    """Pair up nemesis start/stop ops into [start, stop] op intervals,
    FIFO — first start pairs with first stop, like the reference's
    queue-based pairing (util.clj:593-610)."""
    starts: list = []
    intervals = []
    for op in history:
        if op.process != "nemesis":
            continue
        if op.f == "start":
            starts.append(op)
        elif op.f == "stop" and starts:
            intervals.append((starts.pop(0), op))
    for s in starts:
        intervals.append((s, None))
    return intervals


# ---------------------------------------------------------------------------
# Liveness progress counter (bench probe watchdog).
#
# The device engines tick this at every host-visible step (chunk batch,
# host-row closure dispatch, spike mini-chunk, dense chunk, batched key
# group). A monitoring thread (bench.py probe children) samples it: the
# counter advancing proves dispatches are completing, so a stalled value
# discriminates a WEDGED dispatch from a merely long-running but
# progressing search. Monotonic, process-local, monitoring-grade
# (GIL-atomic increments; no lock).

_progress = 0


def progress_tick() -> None:
    """Record one unit of engine forward progress (see above)."""
    global _progress
    _progress += 1


def progress() -> int:
    """Current progress counter value (monotonic within a process)."""
    return _progress


def env_int(name: str, default: int) -> int:
    """Integer env knob with a default (empty/unset -> default). The
    engines re-read knobs per check so monkeypatch.setenv and
    ``env VAR=...`` always take effect — doc/env.md tables them all."""
    import os

    v = os.environ.get(name, "")
    return int(v) if v else default


def env_float(name: str, default: float) -> float:
    """Float twin of :func:`env_int`."""
    import os

    v = os.environ.get(name, "")
    return float(v) if v else default


def file_needs_newline_heal(path: str) -> bool:
    """True when an append-only JSONL file's last byte exists and is
    not a newline — a SIGKILL-torn tail that would glue the next
    record onto the torn line and corrupt BOTH. The one crash-recovery
    rule shared by the service journal and the perf ledger (their
    append paths must never drift). Missing/empty files need no
    heal."""
    import os

    try:
        with open(path, "rb") as fh:
            fh.seek(-1, os.SEEK_END)
            return fh.read(1) not in (b"\n", b"")
    except OSError:
        return False


def write_json_atomic(path: str, obj, default=None) -> None:
    """Atomic JSON file write: pid-suffixed tmp + ``os.replace`` (the
    quarantine-ledger / service-stats / txn-snapshot pattern — last
    writer wins, readers never see a torn file). Raises on failure;
    observability-grade callers swallow at their own site."""
    import json
    import os

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(obj, fh, indent=1, sort_keys=True, default=default)
    os.replace(tmp, path)


def stat_bump(stats: dict, key: str, n: int = 1) -> None:
    """Accumulate an integer observability counter in a stats dict
    (host-row executor episode/dispatch/pass/waste counters — see
    bfs._host_rows). Missing keys start at 0, so call sites never need
    setdefault choreography."""
    stats[key] = stats.get(key, 0) + n


def stat_time(stats: dict, key: str, bucket, seconds: float) -> None:
    """Accumulate wall seconds into a per-bucket timing histogram
    ``stats[key][bucket]`` (e.g. per-capacity closure wall time,
    bucket = the cap). Raw float accumulation — round at reporting
    time (round_stats), not per sample."""
    d = stats.setdefault(key, {})
    d[bucket] = d.get(bucket, 0.0) + seconds


def round_stats(stats: dict, ndigits: int = 2) -> dict:
    """Artifact-ready copy of a stats dict: floats rounded recursively
    through ANY depth of nested dicts/lists (the timing histograms, the
    supervise event trip log, the obs registry views), every other
    value preserved as-is. The engines accumulate raw floats so
    precision is not lost sample by sample; verdicts, bench JSON, and
    registry snapshots carry the rounded copy. Tuples come back as
    lists (the copy is JSON-bound anyway)."""

    def rec(v):
        if isinstance(v, dict):
            return {k: rec(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [rec(x) for x in v]
        if isinstance(v, float):
            return round(v, ndigits)
        return v

    return {k: rec(v) for k, v in stats.items()}


# ---------------------------------------------------------------------------
# Process-wide XLA compile meter.
#
# One shared wrap of jax's ``backend_compile_and_load`` (a TRUE
# compile: a persistent-cache MISS reaching XLA — cache hits load in
# milliseconds and never reach it). Three consumers used to keep
# divergent private copies counting the same thing: tests/conftest.py's
# quick-tier no-compile enforcement, the checker daemon's service stats,
# and the obs metrics registry. ``add_compile_hook`` lets the flight
# recorder (jepsen_tpu.obs.trace) record each compile as a trace event
# without util importing obs (the hook is registered from obs side).

_compile_meter = {"installed": False, "n": 0, "seconds": 0.0, "gets": 0}
_compile_hooks: list = []


def add_compile_hook(fn) -> None:
    """Register ``fn(t0_monotonic, dur_s)`` to run after every true
    XLA compile (exceptions swallowed — hooks are observability)."""
    if fn not in _compile_hooks:
        _compile_hooks.append(fn)


def install_compile_meter() -> bool:
    """Idempotently wrap jax 0.9's ``backend_compile_and_load`` (every
    true compile) and ``compile_or_get_cached`` (every compile request:
    the difference is persistent-cache hits) with the count/seconds
    meter. Returns True."""
    import time

    import jax._src.compiler as _jc

    if _compile_meter["installed"]:
        return True
    _compile_meter["installed"] = True
    real = _jc.backend_compile_and_load
    real_get = _jc.compile_or_get_cached

    def metered(*a, **kw):
        t0 = time.monotonic()
        try:
            return real(*a, **kw)
        finally:
            dur = time.monotonic() - t0
            _compile_meter["n"] += 1
            _compile_meter["seconds"] += dur
            for fn in list(_compile_hooks):
                try:
                    fn(t0, dur)
                except Exception:  # noqa: BLE001 - observability hook
                    pass

    def counted_get(*a, **kw):
        _compile_meter["gets"] += 1
        return real_get(*a, **kw)

    _jc.backend_compile_and_load = metered
    _jc.compile_or_get_cached = counted_get
    return True


def compile_meter() -> dict:
    """Snapshot of the process-wide XLA compile meter (zeros when the
    wrap never installed)."""
    n = _compile_meter["n"]
    hits = None
    if _compile_meter["installed"] and _compile_meter["gets"] >= n:
        hits = _compile_meter["gets"] - n
    return {"xla_compiles": n,
            "xla_compile_s": round(_compile_meter["seconds"], 2),
            "xla_cache_hits": hits}


def compile_counts() -> tuple[int, int] | None:
    """``(requests, compiles)`` so far: in-memory jit misses that asked
    for a compile, and those that reached XLA (persistent-cache hits
    are the difference). None while the meter is not installed."""
    if not _compile_meter["installed"]:
        return None
    return _compile_meter["gets"], _compile_meter["n"]


def cache_dir() -> str:
    """``<repo>/.jax_cache`` — the one anchor for every on-disk
    artifact (compile cache, quarantine ledger, service stats, trace
    spills, telemetry snapshots). Not created here; writers makedirs
    on first use."""
    import os

    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def enable_compile_cache() -> str:
    """Enable JAX's persistent compilation cache.

    The linearizability engines compile one program per (cap, window,
    state-bucket) shape; each costs tens of seconds of XLA time on first
    use and is bit-identical across processes, so the cache turns every
    cold start after the first into a warm one. The directory is
    ``JAX_COMPILATION_CACHE_DIR`` where that is set, and the fixed
    :func:`cache_dir` otherwise (the path is part of the cache's key: a
    directory that moves never hits). Safe to call multiple times;
    returns the directory.
    """
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
