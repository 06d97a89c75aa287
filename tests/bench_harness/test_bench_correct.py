"""How ``correct`` is decided: the plain reference, its control, and the
faults a run can have, each of which has to come out as not correct."""

from __future__ import annotations

import random

import pytest

from bench_helpers import CELL, REPO, isolated, make_root  # noqa: F401

from benchmark import harness
from benchmark.reference import cas_register as ref
from benchmark.traffic import registers as gen

SEED = 2**40 + 11


@pytest.mark.parametrize("seed", [0, 7, 2**35 + 1])
@pytest.mark.parametrize("conc,crash", [(5, 0.01), (30, 0.002)])
def test_reference_certifies_generated_histories(seed, conc, crash):
    g = gen.generate(600, conc, seed, 5, crash, 4)
    assert ref.expected(g.events, g.lin, None) == {"valid?": True}
    bad = gen.corrupt(g, random.Random(seed).randrange(gen.rows(g)))
    want = ref.expected(bad.events, bad.lin, bad.corrupted)
    t, f, v, p = bad.events[bad.corrupted]
    assert want["valid?"] is False and want["op"]["process"] == p
    assert bad.events[want["op"]["index"]][:2] == ("invoke", "read")
    with pytest.raises(ref.CertificateError):
        ref.expected(bad.events, bad.lin, None)


@pytest.mark.parametrize("seed", [1, 2**40 + 9])
def test_pools_hold_the_same_sizes_and_late_corruptions_for_every_seed(
        seed):
    """The committed cell's traffic at 20k-op histories: the same strata
    for every seed, and each corrupted copy's bad read in the history's
    second half, a few rows past a boundary of ``align_rows``."""
    import json

    config = json.loads(
        (REPO / "benchmark/configs/cas-register-c5.json").read_text())
    config["history"].update(n_ops=20000, crash_prob=0.005)
    traffic = json.loads((REPO / "benchmark/workloads" /
                          f"{CELL['traffic']}.json").read_text())
    traffic["corrupt"]["align_rows"] = 2048
    pool = harness.build_pool(gen, config, traffic, seed)
    valid = [i for i in pool if i.gen.corrupted is None]
    bad = [i for i in pool if i.gen.corrupted is not None]
    want = traffic["strata"]["crashed_mutators"]
    assert sorted(gen.strata(i.gen)["crashed_mutators"]
                  for i in valid) == sorted(want)
    assert sorted(gen.strata(i.gen)["crashed_mutators"] for i in bad) == \
        sorted(want[j] for j in traffic["corrupt"]["of"])
    for i in valid:
        assert ref.expected(i.gen.events, i.gen.lin, None)["valid?"]
    for i in bad:
        row = sum(1 for e in i.gen.events[:i.gen.corrupted] if e[0] == "ok")
        assert row > i.rows // 2
        assert row % 2048 < traffic["corrupt"]["within"] + 16
        assert ref.expected(i.gen.events, i.gen.lin,
                            i.gen.corrupted)["valid?"] is False


def test_reference_rejects_a_certificate_out_of_order():
    g = gen.generate(300, 5, 3, 5, 0.0, 0)
    lin = list(g.lin)
    lin[10], lin[20] = lin[20], lin[10]
    with pytest.raises(ref.CertificateError):
        ref.expected(g.events, lin, None)


def test_path_check_catches_illegal_and_foreign_paths():
    events = [("invoke", "write", 1, 0), ("ok", "write", 1, 0),
              ("invoke", "read", None, 1), ("ok", "read", 1, 1),
              ("invoke", "cas", [2, 3], 0), ("fail", "cas", [2, 3], 0)]
    op = lambda i, f, v, p: {"index": i, "f": f, "value": v,  # noqa: E731
                             "process": p, "ok": True}
    good = {"model": 1, "path": [op(0, "write", 1, 0), op(2, "read", 1, 1)]}
    fault = lambda *p: ref.paths_fault(events, list(p))  # noqa: E731
    assert fault(good) is None
    assert ref.paths_fault(events, []) and ref.paths_fault(events, None)
    assert fault(good, {"model": 2, "path": good["path"]})
    assert fault({"model": 3, "path": good["path"] + [
        op(4, "cas", [2, 3], 0)]})                       # a failed op
    assert fault({"model": 1, "path": [
        op(0, "write", 1, 0), op(0, "write", 1, 0)]})     # repeated
    assert fault({"model": 1, "path": [
        op(1, "write", 1, 0)]})                          # not an invocation
    assert fault({"model": 1, "path": [
        op(2, "read", 1, 1), op(9, "read", 1, 1)]})      # not in history


def test_control_comes_out_not_correct(tmp_path, isolated):  # noqa: F811
    """The control: the checker given every crashed op as failed, i.e.
    as certainly not having taken effect."""
    root = make_root(tmp_path)
    out = harness.measure(root, "tiny", SEED, 0.5, False, 0.0,
                          transform=gen.crashes_as_failed, require_tpu=False)
    assert out["correct"] is False
    assert out["checks"]["wrong_verdicts"]["value"] > 0


def _flip_verdict(monkeypatch):
    from jepsen_tpu import lin

    real = lin.device_check_packed

    def fault(*a, **kw):
        r = dict(real(*a, **kw))
        r["valid?"] = not r["valid?"]
        return r
    monkeypatch.setattr(lin, "device_check_packed", fault)


def _alter_named_op(monkeypatch):
    from jepsen_tpu import lin

    real = lin.device_check_packed

    def fault(*a, **kw):
        r = dict(real(*a, **kw))
        if r.get("op"):
            r["op"] = dict(r["op"], index=r["op"]["index"] + 1)
        return r
    monkeypatch.setattr(lin, "device_check_packed", fault)


def _half_history(monkeypatch):
    from jepsen_tpu.lin import prepare

    real = prepare.prepare

    def fault(model, history, *a, **kw):
        history = list(history)
        return real(model, history[:len(history) // 2], *a, **kw)
    monkeypatch.setattr(prepare, "prepare", fault)


def _state_unchanged(monkeypatch):
    """Each dense step hands back the frontier it was given; small
    chunks so that a tiny history takes many steps."""
    import functools

    from jepsen_tpu.lin import dense

    real_step = dense._dense_chunk

    def step(F, *a, **kw):
        _, r, dead, trunc = real_step(F, *a, **kw)
        return F, r, dead, trunc
    monkeypatch.setattr(dense, "_dense_chunk", step)
    monkeypatch.setattr(dense, "check_packed",
                        functools.partial(dense.check_packed, chunk=64))


@pytest.mark.parametrize("plant", [
    _flip_verdict, _alter_named_op, _half_history, _state_unchanged])
def test_a_broken_timed_path_comes_out_not_correct(
        tmp_path, isolated, monkeypatch, plant):  # noqa: F811
    """Each fault under the committed cell's traffic, cut to test size."""
    root = make_root(tmp_path)
    plant(monkeypatch)
    out = harness.measure(root, "tiny", SEED, 0.5, False, 0.0,
                          require_tpu=False)
    assert out["correct"] is False, out["checks"]
