"""Flight recorder (jepsen_tpu/obs/ — doc/observability.md): the span
tracer's disabled/enabled contracts, nesting and thread safety, the
Chrome trace-event export, the metrics registry snapshot round trip,
the attribution report, and the supervise-layer integration — plus the
JEPSEN_TPU_WEDGE e2e asserting the wedge/retry/fallback ladder shows
up as dispatch spans with the right outcomes.

The unit tests are pure host Python (quick, no XLA); the e2e ladder
and parity tests drive the real engines on tiny .jax_cache-resident
shapes and carry the registered ``compiles`` marker (the
test_lin_supervise precedent)."""

import json
import os
import threading
import time

import pytest

from jepsen_tpu import util
from jepsen_tpu.obs import metrics, report, trace

pytestmark = pytest.mark.quick


@pytest.fixture(autouse=True)
def _obs_sandbox(monkeypatch):
    """Tracing off, no spill file, no telemetry snapshot file — every
    test opts in explicitly and leaves no state behind."""
    monkeypatch.delenv("JEPSEN_TPU_TRACE", raising=False)
    monkeypatch.setenv("JEPSEN_TPU_TRACE_FILE", "0")
    monkeypatch.setenv("JEPSEN_TPU_OBS_SNAPSHOT", "0")
    trace.reset()
    metrics.REGISTRY.reset()
    yield
    trace.reset()
    metrics.REGISTRY.reset()


def _on(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_TRACE", "1")


# --- tracer: disabled path --------------------------------------------------


def test_disabled_span_is_one_shared_null_object(monkeypatch):
    # The disabled-path promise (doc/observability.md): span() returns
    # the SAME object every call — no per-span allocation, no buffer
    # write. Identity is the allocation-free proof.
    assert not trace.enabled()
    s1 = trace.span("a", site="x")
    s2 = trace.span("b")
    assert s1 is s2 is trace.NULL_SPAN
    with s1 as sp:
        sp.note(outcome="ok")
    trace.instant("i", x=1)
    trace.complete("c", 0.0, 1.0)
    trace.tail_note(x=2)
    assert trace.events() == []


def test_disabled_span_overhead_is_flat(monkeypatch):
    # 100k disabled spans must stay far under any engine-visible cost
    # (the quick tier's "no measurable slowdown" acceptance bar —
    # generous bound so a loaded CI box cannot flake it).
    t0 = time.monotonic()
    for _ in range(100_000):
        with trace.span("x"):
            pass
    assert time.monotonic() - t0 < 2.0
    assert trace.events() == []


# --- tracer: enabled spans --------------------------------------------------


def test_span_records_event_with_args(monkeypatch):
    _on(monkeypatch)
    with trace.span("dispatch", site="chunk", shape="chunk|cap8") as sp:
        sp.note(outcome="ok", passes=3)
    evs = trace.events()
    assert len(evs) == 1
    ev = evs[0]
    assert ev["name"] == "dispatch" and ev["ph"] == "X"
    assert ev["dur"] >= 0 and ev["ts"] > 0
    assert ev["args"] == {"site": "chunk", "shape": "chunk|cap8",
                          "outcome": "ok", "passes": 3}


def test_span_exception_stamps_error_outcome(monkeypatch):
    _on(monkeypatch)
    with pytest.raises(ValueError):
        with trace.span("dispatch", site="chunk"):
            raise ValueError("boom")
    ev = trace.events()[0]
    assert ev["args"]["outcome"] == "error:ValueError"
    # A site-noted outcome wins over the exception stamp.
    with pytest.raises(RuntimeError):
        with trace.span("dispatch", site="chunk") as sp:
            sp.note(outcome="fault")
            raise RuntimeError("worker died")
    assert trace.events()[1]["args"]["outcome"] == "fault"


def test_span_nesting_depth(monkeypatch):
    _on(monkeypatch)
    with trace.span("check"):
        with trace.span("dispatch"):
            pass
    inner, outer = trace.events()
    assert inner["name"] == "dispatch" and inner["depth"] == 1
    assert outer["name"] == "check" and outer["depth"] == 0


def test_tail_note_annotates_last_completed_event(monkeypatch):
    _on(monkeypatch)
    with trace.span("dispatch", site="host-fixpoint"):
        pass
    trace.tail_note(row=7, count=130)
    ev = trace.events()[0]
    assert ev["args"]["row"] == 7 and ev["args"]["count"] == 130


def test_thread_safety_every_span_lands_once(monkeypatch):
    _on(monkeypatch)
    n_threads, n_spans = 8, 200
    errs: list = []

    def work(tid):
        try:
            for i in range(n_spans):
                with trace.span("dispatch", site=f"t{tid}") as sp:
                    sp.note(i=i)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    ts = [threading.Thread(target=work, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    evs = trace.events()
    assert len(evs) == n_threads * n_spans
    for k in range(n_threads):
        mine = [e for e in evs if e["args"].get("site") == f"t{k}"]
        assert sorted(e["args"]["i"] for e in mine) == list(range(n_spans))
        # Each thread's spans never nested: depth stays 0.
        assert all(e["depth"] == 0 for e in mine)


def test_ring_buffer_drops_oldest_without_spill_file(monkeypatch):
    _on(monkeypatch)
    monkeypatch.setenv("JEPSEN_TPU_TRACE_BUF", "16")
    for i in range(50):
        trace.instant("tick", i=i)
    evs = trace.events()
    assert len(evs) == 16
    assert [e["args"]["i"] for e in evs] == list(range(34, 50))


def test_spill_file_keeps_everything_and_flushes(tmp_path, monkeypatch):
    _on(monkeypatch)
    spill = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("JEPSEN_TPU_TRACE_FILE", spill)
    for i in range(10):
        with trace.span("dispatch", site="chunk") as sp:
            sp.note(i=i)
    assert trace.flush() == spill
    loaded = report.load(spill)
    assert [e["args"]["i"] for e in loaded] == list(range(10))
    # A killed run's torn last line is skipped, not fatal.
    with open(spill, "a") as fh:
        fh.write('{"name": "torn", "ph"')
    assert len(report.load(spill)) == 10
    # reset + a new run truncates: one process/run per file.
    trace.reset()
    trace.instant("fresh")
    trace.flush()
    loaded = report.load(spill)
    assert len(loaded) == 1 and loaded[0]["name"] == "fresh"


def test_spill_batch_keeps_tail_for_late_notes(tmp_path, monkeypatch):
    # The batch spill leaves the newest _SPILL_KEEP events in memory
    # so an after-the-fact tail_note still reaches the file copy; the
    # final flush writes everything exactly once.
    _on(monkeypatch)
    spill = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("JEPSEN_TPU_TRACE_FILE", spill)
    for i in range(trace._SPILL_BATCH):
        trace.instant("tick", i=i)
    assert trace.spilled() == trace._SPILL_BATCH - trace._SPILL_KEEP
    trace.tail_note(late=True)
    trace.flush()
    loaded = report.load(spill)
    assert len(loaded) == trace._SPILL_BATCH
    assert [e["args"]["i"] for e in loaded] == list(
        range(trace._SPILL_BATCH))
    assert loaded[-1]["args"]["late"] is True


def test_spill_failure_latches_to_in_memory_ring(tmp_path, monkeypatch):
    # An unwritable spill path must degrade ONCE to the ring (the
    # _file_dead latch) — not re-serialize the whole backlog on every
    # later record under the tracer lock.
    _on(monkeypatch)
    blocker = tmp_path / "blocker"
    blocker.write_text("")          # a FILE where a directory must go
    monkeypatch.setenv("JEPSEN_TPU_TRACE_FILE",
                       str(blocker / "trace.jsonl"))
    monkeypatch.setenv("JEPSEN_TPU_TRACE_BUF", "128")
    for i in range(trace._SPILL_BATCH + 200):
        trace.instant("tick", i=i)
    assert trace._file_dead is True
    assert trace.spilled() == 0
    evs = trace.events()
    assert len(evs) == 128          # the ring bound, newest kept
    assert evs[-1]["args"]["i"] == trace._SPILL_BATCH + 199


# --- tracer: request scope --------------------------------------------------


def test_in_request_span_records_into_the_summary_untraced():
    # JEPSEN_TPU_TRACE unset: outside a request span() stays the null
    # object; inside one it is real and feeds the request's summary,
    # with self time = duration less what child spans cover.
    assert not trace.enabled()
    assert trace.span("outside") is trace.NULL_SPAN
    trace.count("outside")
    with trace.request() as req:
        with trace.span("outer"):
            with trace.span("inner"):
                time.sleep(0.002)
            trace.count("things", 3)
        trace.count("things")
    assert trace.span("after") is trace.NULL_SPAN
    rec = req.record()
    spans, counts = rec["spans"], rec["counts"]
    assert set(spans) == {"analysis", "outer", "inner"}
    assert counts["things"] == 4 and "outside" not in counts
    inner, outer, root = spans["inner"], spans["outer"], spans["analysis"]
    assert inner["n"] == outer["n"] == root["n"] == 1
    assert inner["s"] >= 0.002 and inner["self_s"] == inner["s"]
    assert outer["self_s"] == pytest.approx(outer["s"] - inner["s"])
    assert sum(v["self_s"] for v in spans.values()) == \
        pytest.approx(root["s"])
    assert trace.events() == []       # the JSONL recorder stayed off


def test_request_summary_loses_no_update_across_threads():
    # Threads run in copies of the request's context (as the
    # competition racers do) and update one summary; a lost update
    # would show in the totals.
    import contextvars
    import sys

    n_threads, n_spans = 24, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with trace.request() as req:
            def work():
                for _ in range(n_spans):
                    with trace.span("racer"):
                        trace.count("ticks")

            ts = [threading.Thread(target=contextvars.copy_context().run,
                                   args=(work,))
                  for _ in range(n_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    rec = req.record()
    assert rec["spans"]["racer"]["n"] == n_threads * n_spans
    assert rec["counts"]["ticks"] == n_threads * n_spans
    # The root's self time never reads below zero, though the racers'
    # spans together may cover more than its duration.
    assert rec["spans"]["analysis"]["self_s"] >= 0


def test_request_spans_reach_the_recorder_with_req_and_parent(monkeypatch):
    _on(monkeypatch)
    with trace.request() as req:
        with trace.span("prepare"):
            pass
    prep, root = trace.events()
    assert prep["name"] == "prepare" and root["name"] == "analysis"
    assert prep["req"] == root["req"] == req.id
    assert prep["parent"] == "analysis" and root["parent"] is None
    assert prep["depth"] == 1 and root["depth"] == 0


def test_request_spans_are_profiler_annotations(tmp_path):
    # Inside a profiler session the in-request spans lie on the host
    # plane as jepsen.<span>, nested in an enclosing annotation, on one
    # clock.
    import jax
    from jax.profiler import ProfileData

    from jepsen_tpu import models as m
    from jepsen_tpu.lin import prepare, synth

    h = synth.generate_register_history(600, concurrency=4, seed=3,
                                        value_range=3, crash_prob=0.05,
                                        max_crashes=6)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("outer"):
            with trace.request() as req:
                prepare.prepare(m.cas_register(), h)
    pb, = tmp_path.glob("**/*.xplane.pb")
    found: dict = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in ("outer", "jepsen.analysis", "jepsen.prepare",
                              "jepsen.prepare.pair"):
                    found[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    (o0, o1, _), (p0, p1, stats) = found["outer"], found["jepsen.prepare"]
    assert o0 <= p0 < p1 <= o1
    assert stats.get("req") == req.id
    a0, a1, _ = found["jepsen.analysis"]
    q0, q1, _ = found["jepsen.prepare.pair"]
    assert o0 <= a0 <= p0 <= q0 < q1 <= p1 <= a1 <= o1


def _history_600(corrupt: bool):
    from jepsen_tpu.lin import synth

    h = synth.generate_register_history(600, concurrency=4, seed=3,
                                        value_range=3, crash_prob=0.05,
                                        max_crashes=6)
    return synth.corrupt_history(h, seed=3) if corrupt else h


@pytest.mark.compiles
@pytest.mark.parametrize("corrupt", [False, True])
def test_verdict_carries_the_request_record(corrupt):
    # Every lin.analysis verdict carries spans and counts; on this
    # single-threaded request the self times add up to the root's
    # duration, and dense.rows counts the rows swept: all of a valid
    # history's, an invalid one's up to its dead row, which the witness
    # then replays from its chunk's entry bitmap.
    from jepsen_tpu import checker, models as m
    from jepsen_tpu.lin import prepare

    h = _history_600(corrupt)
    r = checker.linearizable(algorithm="tpu").check(
        None, m.cas_register(), h, {})
    spans, counts = r["spans"], r["counts"]
    assert r["valid?"] is (not corrupt) and r["analyzer"] == "tpu-dense"
    assert {"analysis", "prepare", "prepare.pair", "prepare.kernelize",
            "prepare.walk", "prepare.tables", "check", "dense.prep",
            "dense.wait", "dispatch"} <= set(spans)
    assert sum(v["self_s"] for v in spans.values()) == pytest.approx(
        spans["analysis"]["s"], abs=1e-3)
    assert spans["dispatch"]["n"] == counts["dense.chunks"] >= 1
    rows = r["dead-row"] + 1 if corrupt else \
        prepare.prepare(m.cas_register(), h).R
    assert counts["dense.rows"] == rows
    assert counts["compile_requests"] >= counts["compiles"] >= 0
    if corrupt:
        assert r["final-paths"]
        assert {"witness", "witness.decode", "witness.replay"} <= set(spans)
        assert counts["witness.rows"] == rows      # one chunk, base 0
        assert counts["witness.configs"] >= 1
    else:
        assert not {"witness", "witness.rows"} & (set(spans) | set(counts))


@pytest.mark.compiles
def test_competition_racers_report_into_the_request():
    # The racer threads run in copies of the request's context: the
    # device racer's check span reaches the verdict's record.
    from jepsen_tpu import lin, models as m

    r = lin.analysis(m.cas_register(), _history_600(False))
    assert r["valid?"] is True
    assert {"analysis", "prepare", "check"} <= set(r["spans"])


# --- chrome export ----------------------------------------------------------


def _chrome_is_structurally_valid(chrome):
    assert isinstance(chrome, dict)
    evs = chrome["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int) and 0 <= ev["tid"] < 2**31
        assert isinstance(ev["name"], str)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        else:
            assert ev["s"] == "t"
    # The whole document must survive a JSON round trip (what
    # Perfetto actually parses).
    assert json.loads(json.dumps(chrome))["traceEvents"]


def test_chrome_export_valid_and_rebased(monkeypatch):
    _on(monkeypatch)
    with trace.span("check", engine="sparse"):
        with trace.span("dispatch", site="chunk") as sp:
            sp.note(outcome="ok")
        trace.instant("wasted-rung", cap=8, seconds=0.1)
    chrome = report.to_chrome(trace.events())
    _chrome_is_structurally_valid(chrome)
    # Rebased to t=0 in MICROseconds; site folded into the name.
    assert min(e["ts"] for e in chrome["traceEvents"]) == 0.0
    names = {e["name"] for e in chrome["traceEvents"]}
    assert "dispatch:chunk" in names and "check" in names


# --- attribution ------------------------------------------------------------


def _ev(name, dur, ph="X", **args):
    return {"name": name, "ph": ph, "ts": 1.0, "dur": dur,
            "pid": 1, "tid": 1, "depth": 0, "args": args}


def test_attribution_aggregates_sites_caps_and_waste():
    evs = [
        _ev("check", 10.0, engine="sparse"),
        _ev("dispatch", 2.0, site="chunk", outcome="ok",
            shape="chunk|rows512|cap8|w34|cas-register"),
        _ev("dispatch", 3.0, site="chunk", outcome="ok",
            shape="chunk|rows512|cap64|w34|cas-register"),
        _ev("dispatch", 1.0, site="host-fixpoint", outcome="wedge",
            shape="host-fixpoint|cap4096|w34|cas-register"),
        _ev("xla-compile", 1.5),
        _ev("wasted-rung", 0.0, ph="i", cap=8, seconds=0.4),
        _ev("host-episode", 2.5, row=10),
    ]
    agg = report.attribution(evs)
    assert agg["total_s"] == 10.0 and agg["checks"] == 1
    assert agg["dispatch_s"] == 6.0 and agg["dispatches"] == 3
    assert agg["compile_s"] == 1.5 and agg["compiles"] == 1
    # Wasted = the wedged dispatch's wall + the wasted-rung instant.
    assert agg["wasted_s"] == pytest.approx(1.4)
    assert agg["wasted_events"] == 2
    chunk = agg["sites"]["chunk"]
    assert chunk["n"] == 2 and chunk["ok"] == 2
    assert chunk["caps"] == {8: 2.0, 64: 3.0}
    hf = agg["sites"]["host-fixpoint"]
    assert hf["wedge"] == 1 and hf["caps"] == {4096: 1.0}
    # Host spans give no device busy time: no estimate is reported
    # (that number comes from a profiler trace of a chip run).
    assert not any(k.startswith("device_busy") for k in agg)
    # host/other closes the books: sites + host_other == check wall.
    assert agg["host_other_s"] == pytest.approx(10.0 - 6.0)
    assert agg["dispatch_s"] + agg["host_other_s"] == pytest.approx(
        agg["total_s"])
    # Non-dispatch spans surface under "other".
    assert agg["other"]["host-episode"] == {"n": 1, "wall_s": 2.5}


def test_render_and_summary():
    evs = [_ev("check", 5.0),
           _ev("dispatch", 2.0, site="chunk", outcome="ok",
               shape="chunk|cap8|w20|k")]
    agg = report.attribution(evs)
    text = report.render(agg)
    assert "check wall total" in text
    assert "chunk" in text and "device busy" not in text
    s = report.summary(evs)
    assert s["total_s"] == 5.0 and s["site_s"] == {"chunk": 2.0}
    assert "dispatch_s" in s and "compile_s" in s


def test_attribution_episode_dispatch_histogram():
    # The per-episode dispatch histogram (ISSUE 14 satellite): bfs
    # stamps host-stats dispatch/row deltas on each host-episode
    # span; attribution buckets dispatches/episode so the episode
    # scheduler's drop reads straight off a probe-config5 trace.
    evs = [
        _ev("check", 10.0),
        _ev("host-episode", 2.0, row=0, dispatches=1, rows=30),
        _ev("host-episode", 2.0, row=30, dispatches=2, rows=32),
        _ev("host-episode", 2.0, row=62, dispatches=9, rows=12),
    ]
    agg = report.attribution(evs)
    ep = agg["episodes"]
    assert ep["n"] == 3 and ep["dispatches"] == 12
    assert ep["rows"] == 74
    assert ep["dispatches_per_episode"] == 4.0
    assert ep["rows_per_dispatch"] == round(74 / 12, 2)
    assert ep["histogram"] == {"1": 1, "2-3": 1, "8-15": 1}
    text = report.render(agg)
    assert "host episodes" in text and "dispatches/episode" in text
    # Episodes WITHOUT the deltas (pre-ISSUE-14 traces) keep the old
    # "other" row and no episodes block.
    agg2 = report.attribution([_ev("check", 1.0),
                               _ev("host-episode", 0.5, row=0)])
    assert "episodes" not in agg2
    assert agg2["other"]["host-episode"]["n"] == 1


# --- metrics registry -------------------------------------------------------


def test_registry_views_are_live_references():
    stats = {"rows": 0}
    metrics.REGISTRY.view("host-stats", stats)
    stats["rows"] = 7
    snap = metrics.REGISTRY.snapshot()
    assert snap["views"]["host-stats"]["rows"] == 7
    # Re-registering swaps the reference (a fresh check run).
    metrics.REGISTRY.view("host-stats", {"rows": 1})
    assert metrics.REGISTRY.snapshot()["views"]["host-stats"]["rows"] == 1


def test_registry_progress_rates_and_eta():
    r = metrics.REGISTRY
    r.start_run("lin-sparse", total=100, window=34)
    r._samples.append((0.0, 0, 10))      # pin elapsed for determinism
    r._samples.append((2.0, 40, 500))
    snap = r.snapshot()
    assert snap["run"]["total_rows"] == 100
    assert snap["run"]["rows_per_sec"] == pytest.approx(20.0)
    assert snap["run"]["eta_s"] == pytest.approx(3.0)
    assert snap["samples"][-1] == [2.0, 40, 500]


def test_registry_event_feed_is_bounded():
    for i in range(metrics.MAX_EVENTS + 10):
        metrics.REGISTRY.event("wedge", site="chunk", i=i)
    evs = metrics.REGISTRY.snapshot()["events"]
    assert len(evs) == metrics.MAX_EVENTS
    assert evs[-1]["i"] == metrics.MAX_EVENTS + 9
    assert evs[0]["kind"] == "wedge" and evs[0]["site"] == "chunk"


def test_registry_snapshot_round_trip(tmp_path, monkeypatch):
    path = str(tmp_path / "telemetry.json")
    r = metrics.REGISTRY
    r.start_run("lin-sparse", total=50)
    r.view("host-stats", {"rows": 3, "cap_seconds": {8: 1.234567}})
    r.counter("ticks", 2)
    r.gauge("row", 3)
    r.event("quarantine", key="chunk|cap8")
    r.write_snapshot(path=path, force=True)
    snap, err = metrics.load_json_snapshot(path)
    assert err is None
    assert snap["run"]["run"] == "lin-sparse"
    assert snap["run"]["row"] == 3
    # round_stats flowed through the codec (3 digits, nested).
    assert snap["views"]["host-stats"]["cap_seconds"] == {"8": 1.235}
    # Every event bumps a durable event_<kind> counter (the ring
    # holds MAX_EVENTS; the counter survives eviction).
    assert snap["counters"] == {"ticks": 2, "event_quarantine": 1}
    assert snap["events"][0]["key"] == "chunk|cap8"
    assert "xla_compiles" in snap


def test_snapshot_first_write_is_interval_gated(tmp_path, monkeypatch):
    # The "short runs and tests write nothing" promise includes the
    # FIRST write: a run younger than JEPSEN_TPU_OBS_EVERY_S must not
    # touch disk (force=True remains the explicit override).
    path = str(tmp_path / "telemetry.json")
    monkeypatch.setenv("JEPSEN_TPU_OBS_SNAPSHOT", path)
    r = metrics.REGISTRY
    r.start_run("lin-sparse", total=10)
    r.progress(row=1, frontier=5)
    assert not os.path.exists(path)
    r.write_snapshot(force=True)
    assert os.path.exists(path)


def test_load_json_snapshot_error_paths(tmp_path):
    snap, err = metrics.load_json_snapshot(str(tmp_path / "missing"))
    assert snap is None and err
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    snap, err = metrics.load_json_snapshot(str(bad))
    assert snap is None and err


# --- util satellites --------------------------------------------------------


def test_round_stats_recurses_and_preserves_non_numeric():
    # Satellite fix: round_stats used to round only ONE level deep and
    # silently mangled deeper nests; it must now recurse through any
    # depth and preserve every non-float value.
    stats = {
        "wall": 1.23456,
        "n": 7,
        "cap_seconds": {8: 0.123456, 4096: 2.999999},
        "tiers": {"ww": {"edges": 10, "decide_s": 0.55555,
                         "fallback": None}},
        "events": [{"site": "chunk", "s": 1.987654},
                   "plain-string"],
        "pair": (1.23456, "x"),
        "label": "cas-register",
    }
    out = util.round_stats(stats)
    assert out["wall"] == 1.23
    assert out["n"] == 7
    assert out["cap_seconds"] == {8: 0.12, 4096: 3.0}
    assert out["tiers"]["ww"] == {"edges": 10, "decide_s": 0.56,
                                  "fallback": None}
    assert out["events"][0] == {"site": "chunk", "s": 1.99}
    assert out["events"][1] == "plain-string"
    assert out["pair"] == [1.23, "x"]       # tuples -> lists (JSON-bound)
    assert out["label"] == "cas-register"
    # The input is untouched (it is the engine's LIVE stats dict).
    assert stats["cap_seconds"][8] == 0.123456


@pytest.mark.compiles
def test_compile_meter_shape_and_idempotent_install():
    assert util.install_compile_meter() is True
    assert util.install_compile_meter() is True      # idempotent
    m = util.compile_meter()
    assert set(m) == {"xla_compiles", "xla_compile_s",
                      "xla_cache_hits"}
    # The meter sees the installed jax's compiles: a fresh program
    # (below the persistent cache's 1 s floor, so never a hit) counts.
    import jax
    import jax.numpy as jnp

    import time

    k = time.time_ns() % 1000003          # a module no cache has seen
    jax.jit(lambda x: x * k - 3)(jnp.arange(5)).block_until_ready()
    assert util.compile_meter()["xla_compiles"] > m["xla_compiles"]


# --- supervise integration --------------------------------------------------


@pytest.fixture()
def _clean_injections():
    from jepsen_tpu.lin import supervise

    supervise._injected.clear()
    yield supervise
    supervise._injected.clear()


def test_supervised_call_emits_dispatch_span(monkeypatch,
                                             _clean_injections):
    supervise = _clean_injections
    _on(monkeypatch)
    assert supervise.call("chunk", lambda: 42, deadline_s=5,
                          shape="chunk|cap8|w20|k") == 42
    ev = trace.events()[0]
    assert ev["name"] == "dispatch"
    assert ev["args"]["site"] == "chunk"
    assert ev["args"]["shape"] == "chunk|cap8|w20|k"
    assert ev["args"]["outcome"] == "ok"


def test_supervised_wedge_retry_visible_in_span(monkeypatch,
                                                _clean_injections):
    supervise = _clean_injections
    _on(monkeypatch)
    supervise.inject_wedge("t", 1, deadline_s=0.1)
    assert supervise.call("t", lambda: "real", deadline_s=9) == "real"
    ev = trace.events()[0]
    assert ev["args"]["outcome"] == "ok"
    assert ev["args"]["wedges"] == 1 and ev["args"]["attempts"] == 2
    assert ev["dur"] >= 0.1      # the span covers the wedged attempt


def test_supervised_exhaustion_and_fault_outcomes(monkeypatch,
                                                  _clean_injections):
    supervise = _clean_injections
    _on(monkeypatch)
    supervise.inject_wedge("t", 5, deadline_s=0.05)
    with pytest.raises(supervise.WedgedDispatch):
        supervise.call("t", lambda: None, deadline_s=9, retries=1)
    ev = trace.events()[0]
    assert ev["args"]["outcome"] == "wedge" and ev["args"]["wedges"] == 2
    supervise._injected.clear()      # drop the unconsumed injections

    def boom():
        raise RuntimeError("worker died")

    with pytest.raises(RuntimeError):
        supervise.call("t", boom, deadline_s=5)
    ev = trace.events()[1]
    assert ev["args"]["outcome"] == "fault"
    assert ev["args"]["error"] == "RuntimeError"


def test_supervise_events_reach_registry_feed(_clean_injections):
    supervise = _clean_injections
    supervise.inject_wedge("t", 1, deadline_s=0.05)
    supervise.call("t", lambda: 1, deadline_s=9)
    evs = metrics.REGISTRY.snapshot()["events"]
    assert any(e["kind"] == "wedge" and e["site"] == "t" for e in evs)


# --- cli / web surfaces -----------------------------------------------------


def _write_trace_file(tmp_path, monkeypatch):
    _on(monkeypatch)
    spill = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("JEPSEN_TPU_TRACE_FILE", spill)
    with trace.span("check", engine="sparse"):
        with trace.span("dispatch", site="chunk",
                        shape="chunk|cap8|w20|k") as sp:
            sp.note(outcome="ok")
    trace.flush()
    return spill


def test_cli_trace_report_and_export(tmp_path, monkeypatch, capsys):
    from jepsen_tpu import cli

    spill = _write_trace_file(tmp_path, monkeypatch)
    cmds = cli.standard_commands()
    assert cli.run(cmds, ["trace", "report", "--file", spill]) == 0
    out = capsys.readouterr().out
    assert "check wall total" in out and "chunk" in out

    assert cli.run(cmds, ["trace", "report", "--file", spill,
                          "--json"]) == 0
    agg = json.loads(capsys.readouterr().out)
    assert agg["checks"] == 1 and "chunk" in agg["sites"]

    out_path = str(tmp_path / "chrome.json")
    assert cli.run(cmds, ["trace", "export", "--chrome",
                          "--file", spill, "-o", out_path]) == 0
    with open(out_path) as fh:
        _chrome_is_structurally_valid(json.load(fh))

    # No events -> loud error, not an empty table.
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert cli.run(cmds, ["trace", "report", "--file", empty]) != 0


def test_cli_host_stats_reads_snapshot(tmp_path, monkeypatch, capsys):
    from jepsen_tpu import cli

    path = str(tmp_path / "telemetry.json")
    r = metrics.REGISTRY
    r.start_run("lin-sparse", total=10)
    r.view("host-stats", {"rows": 4, "wasted_passes": 2})
    r.event("wedge", site="host-fixpoint")
    r.write_snapshot(path=path, force=True)
    cmds = cli.standard_commands()
    assert cli.run(cmds, ["host-stats", "--file", path]) == 0
    out = capsys.readouterr().out
    assert "lin-sparse" in out and "wasted_passes = 2" in out
    assert "wedge" in out

    assert cli.run(cmds, ["host-stats", "--file", path, "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["views"]["host-stats"]["rows"] == 4

    assert cli.run(cmds, ["host-stats", "--file",
                          str(tmp_path / "nope.json")]) != 0


def test_web_run_page_renders_snapshot(tmp_path):
    from jepsen_tpu import web

    path = str(tmp_path / "telemetry.json")
    r = metrics.REGISTRY
    r.start_run("lin-sparse", total=100)
    r.view("host-stats", {"rows": 5})
    for i in range(8):
        r._samples.append((float(i), i * 10, 100 + i))
    r._gauges["row"] = 70
    r.event("quarantine", key="chunk|cap8|w34|k")
    r.write_snapshot(path=path, force=True)
    html = web.run_html(path)
    assert "run telemetry" in html
    assert "lin-sparse" in html
    assert "<svg" in html                    # the frontier sparkline
    assert "quarantine" in html
    assert "host-stats" in html
    # Missing snapshot: an explanatory page, not a traceback.
    html = web.run_html(str(tmp_path / "missing.json"))
    assert "no run-telemetry snapshot" in html


# --- e2e: the ladder as spans on the real engine ----------------------------


@pytest.fixture(scope="module")
def small_band_packed():
    from jepsen_tpu import models as m
    from jepsen_tpu.lin import prepare, synth

    h = synth.generate_register_history(60, concurrency=6, seed=1,
                                        crash_prob=0.25)
    return prepare.prepare(m.cas_register(), h)


@pytest.mark.compiles
def test_e2e_wedge_ladder_appears_as_spans(tmp_path, monkeypatch,
                                           small_band_packed,
                                           _clean_injections):
    # Satellite acceptance: a JEPSEN_TPU_WEDGE-injected engine run,
    # traced, shows the wedge/retry ladder as dispatch spans with the
    # right outcomes — detection + retry on the host-fixpoint site,
    # everything else ok, verdict unchanged.
    supervise = _clean_injections
    from jepsen_tpu.lin import bfs

    _on(monkeypatch)
    monkeypatch.setenv("JEPSEN_TPU_QUARANTINE",
                       str(tmp_path / "quarantine.json"))
    monkeypatch.setenv("JEPSEN_TPU_WEDGE", "host-fixpoint:1:0.3")
    supervise._env_wedge_loaded = None
    r = bfs.check_packed(small_band_packed, cap_schedule=(1,),
                         host_caps=(8, 64, 512))
    supervise._env_wedge_loaded = None
    assert r["valid?"] is True
    assert r["host-stats"]["watchdog_trips"] == 1

    evs = trace.events()
    disp = [e for e in evs if e["name"] == "dispatch"]
    assert disp, "supervised dispatches must appear as spans"
    fx = [e for e in disp if e["args"].get("site") == "host-fixpoint"]
    assert fx, "the host-row fused fixpoint site must be traced"
    # The wedged dispatch: detected, retried, succeeded — one span
    # whose args carry the whole story.
    wedged = [e for e in fx if e["args"].get("wedges")]
    assert len(wedged) == 1
    assert wedged[0]["args"]["outcome"] == "ok"
    assert wedged[0]["args"]["attempts"] == 2
    assert wedged[0]["args"]["shape"].startswith("host-fixpoint|")
    # Every other dispatch is a clean ok (no faults in this run).
    assert all(e["args"].get("outcome") == "ok" for e in disp)
    # The registry event feed saw the trip too (the /run page's
    # triage column).
    feed = metrics.REGISTRY.snapshot()["events"]
    assert any(e["kind"] == "wedge" and e["site"] == "host-fixpoint"
               for e in feed)


@pytest.mark.compiles
def test_e2e_traced_run_attribution_and_parity(monkeypatch):
    # ISSUE acceptance: with JEPSEN_TPU_TRACE=1 a witness-shape
    # device_check_packed run produces (a) an attribution whose
    # per-site rows sum (with host/other) to within 5% of the measured
    # check wall, (b) a structurally valid Chrome export, and (c) the
    # identical verdict/op/final-paths to the untraced run.
    from jepsen_tpu import models as m
    from jepsen_tpu.lin import device_check_packed, prepare, synth

    h = synth.corrupt_history(
        synth.generate_register_history(300, concurrency=12, seed=5,
                                        crash_prob=0.02), seed=2)
    p = prepare.prepare(m.cas_register(), h)

    want = device_check_packed(p, explain=True)      # untraced
    assert trace.events() == []                      # really untraced

    _on(monkeypatch)
    t0 = time.monotonic()
    got = device_check_packed(p, explain=True)
    wall = time.monotonic() - t0

    # (c) identical result with tracing on — observes, never routes.
    assert got["valid?"] == want["valid?"]
    assert got.get("op") == want.get("op")
    assert got.get("final-paths") == want.get("final-paths")

    evs = trace.events()
    agg = report.attribution(evs)
    # (a) the check span covers the run: its wall (= what every site
    # row sums against, dispatch_s + host_other_s) is within 5% of the
    # measured call wall.
    assert agg["checks"] == 1
    # total_s is rounded to the millisecond, which alone is more than
    # 5% of a run this short: compare the span's own duration.
    check_s, = [e["dur"] for e in evs if e["name"] == "check"]
    assert agg["total_s"] == pytest.approx(check_s, abs=5e-4)
    assert check_s == pytest.approx(wall, rel=0.05)
    assert agg["dispatch_s"] + agg["host_other_s"] == pytest.approx(
        agg["total_s"], abs=0.01)      # each term rounded to 3 digits
    assert agg["dispatches"] >= 1 and agg["sites"]
    # (b) the export is valid trace-event JSON.
    _chrome_is_structurally_valid(report.to_chrome(evs))
