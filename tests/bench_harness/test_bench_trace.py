"""The reduction from a profiler trace to the benchmark's device numbers,
on a small trace recorded on the chip (see the fixture's header), and the
table of peaks."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench_helpers import REPO  # noqa: F401

from benchmark.trace import peaks, reduce as treduce

FIXTURE = Path(__file__).with_name("fixture_trace.pbtxt")


@pytest.fixture(scope="module")
def summary():
    return treduce.reduce(treduce.load(str(FIXTURE)))


def _expected():
    """The same numbers worked out from the fixture's events by hand-
    simple code: busy as a union of intervals, gaps as what is left."""
    pd = treduce.load(str(FIXTURE))
    ops, spans = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name.startswith("/device:TPU:") and \
                        line.name == "XLA Ops":
                    ops.append((e.start_ns, e.end_ns, e.name))
                elif e.name.startswith("bench."):
                    spans.append((e.start_ns, e.end_ns, e.name))
    (lo, hi), = [(a, b) for a, b, n in spans if n == "bench.window"]
    ticks = sorted({t for a, b, _ in ops for t in (a, b)} | {lo, hi})
    busy = 0.0
    for a, b in zip(ticks, ticks[1:]):
        if lo <= a and b <= hi and any(s <= a and b <= e for s, e, _ in ops):
            busy += b - a
    return lo, hi, busy, ops


def test_busy_is_the_union_of_device_ops_inside_the_window(summary):
    lo, hi, busy, _ = _expected()
    assert summary.chips == 1
    assert summary.window_ns == pytest.approx(hi - lo)
    assert summary.busy_ns == pytest.approx(busy)
    assert 0 < summary.busy_ns < summary.window_ns


def test_device_time_is_summed_by_operation_name(summary):
    """By instruction name and result shape: the dense kernel at two
    bitmap widths is two operations."""
    lo, hi, _, ops = _expected()
    want: dict = {}
    for a, b, n in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            k = treduce.op_name(n)
            want[k] = want.get(k, 0.0) + (b - a)
    assert summary.op_ns == pytest.approx(want)
    assert "%pallas_chunk.1 u32[128,128]" in summary.op_ns
    assert summary.kernel_ns(lambda n: "pallas_chunk" in n) > 0


def test_idle_gaps_add_up_and_are_named_by_benchmark_spans(summary):
    assert sum(summary.gap_ns.values()) == pytest.approx(
        summary.window_ns - summary.busy_ns)
    assert set(summary.gap_ns) <= {"call", "pack", "witness",
                                   "outside-spans"}
    assert summary.gap_ns.get("pack", 0) > 0
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]


def test_a_trace_without_its_window_is_refused():
    from jax.profiler import ProfileData

    text = FIXTURE.read_text().replace('"bench.window"', '"bench.other"')
    with pytest.raises(ValueError):
        treduce.reduce(ProfileData.from_text_proto(text))


def test_peaks_are_known_for_v5e_and_refused_for_other_devices():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
