"""The readers of the per-layer metrics that come from the program's own
spans and counters: each sums what the verdicts of the window's calls
carry under ``spans``/``counts``, and reads nothing where no verdict
carries it (a program that predates them)."""

from __future__ import annotations

import pytest

from bench_helpers import REPO  # noqa: F401

from benchmark import harness

WINDOW_S = 4.0


def _verdict(prepare, pair, prep, witness=None, compile_requests=0):
    spans = {"analysis": {"n": 1, "s": 9.0, "self_s": 0.1},
             "prepare": {"n": 1, "s": prepare, "self_s": 0.01},
             "prepare.pair": {"n": 1, "s": pair + 0.5, "self_s": pair},
             "dense.prep": {"n": 3, "s": prep + 0.5, "self_s": prep}}
    if witness is not None:
        spans["witness"] = {"n": 1, "s": witness, "self_s": 0.0}
    return {"valid?": witness is None, "analyzer": "tpu-dense",
            "spans": spans,
            "counts": {"compile_requests": compile_requests, "compiles": 0}}


def _run(results):
    calls = [harness.Call(k, 0.0, 1.0, 100, 50, r, None)
             for k, r in enumerate(results)]
    return harness.Run(cell=None, setup_s=1.0, window_s=WINDOW_S,
                       calls=calls, spans={}, compiles_in_window=0)


RESULTS = [_verdict(0.5, 0.25, 0.125, compile_requests=2),
           _verdict(0.75, 0.5, 0.25, witness=0.375),
           {"valid?": "unknown", "error": "a failed call carries no record"}]


@pytest.mark.parametrize("metric,want", [
    ("prepare_span_share", (0.5 + 0.75) / WINDOW_S),
    ("prepare_pair_share", (0.25 + 0.5) / WINDOW_S),
    ("dense_host_share", (0.125 + 0.25) / WINDOW_S),
    ("witness_span_share", 0.375 / WINDOW_S),
    ("compile_requests_in_window", 2),
])
def test_reader_sums_the_window_verdicts(metric, want):
    read = harness.reader(str(REPO), metric).read
    assert read(_run(RESULTS)) == pytest.approx(want)
    # Verdicts without the record (the program before it had one) give
    # nothing to read, not zero.
    assert read(_run([{"valid?": True, "analyzer": "tpu-dense"}])) is None


def test_witness_share_reads_nothing_without_a_counterexample():
    read = harness.reader(str(REPO), "witness_span_share").read
    assert read(_run(RESULTS[:1])) is None
