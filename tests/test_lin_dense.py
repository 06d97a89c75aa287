"""Dense bitmap engine parity tests (on the virtual CPU mesh backend).

The CPU JIT checker (brute-force-verified in test_lin_cpu.py) is the
oracle; the dense engine must agree on every history it accepts —
especially crashed-op histories, which are its headline case (the sparse
path's frontier-inflating worst case costs the bitmap nothing).
"""

import numpy as np
import pytest

from jepsen_tpu import models as m
from jepsen_tpu.history import History, info_op, invoke_op, ok_op
from jepsen_tpu.lin import cpu, dense, dense_pallas, prepare, synth
from jepsen_tpu.obs import trace as obs_trace


def both(model, history, chunk=dense.CHUNK):
    p = prepare.prepare(model, history)
    want = cpu.check_packed(p)["valid?"]
    r = dense.check_packed(p, chunk=chunk)
    assert r["valid?"] == want, f"dense={r} cpu={want}"
    return r["valid?"]


class TestBasics:
    def test_empty(self):
        assert both(m.cas_register(), History.of())

    def test_sequential(self):
        assert both(m.cas_register(), History.of(
            invoke_op(0, "write", 1), ok_op(0, "write", 1),
            invoke_op(0, "read", None), ok_op(0, "read", 1)))

    def test_stale_read_invalid(self):
        p = prepare.prepare(m.cas_register(), History.of(
            invoke_op(0, "write", 1), ok_op(0, "write", 1),
            invoke_op(0, "read", None), ok_op(0, "read", 0)))
        r = dense.check_packed(p)
        assert r["valid?"] is False
        assert r["op"]["f"] == "read" and r["op"]["value"] == 0
        assert r["dead-row"] == 1

    def test_crashed_write_observed(self):
        assert both(m.cas_register(), History.of(
            invoke_op(0, "write", 3), info_op(0, "write", 3),
            invoke_op(1, "read", None), ok_op(1, "read", 3)))

    def test_crashed_write_not_observed(self):
        # crashed op may also never linearize
        assert both(m.cas_register(), History.of(
            invoke_op(0, "write", 7), ok_op(0, "write", 7),
            invoke_op(1, "write", 3), info_op(1, "write", 3),
            invoke_op(2, "read", None), ok_op(2, "read", 7)))

    def test_crashed_cas_chain(self):
        # two crashed ops whose effects must BOTH linearize, in order
        assert both(m.cas_register(), History.of(
            invoke_op(0, "write", 1), ok_op(0, "write", 1),
            invoke_op(1, "write", 2), info_op(1, "write", 2),
            invoke_op(2, "cas", [2, 3]), info_op(2, "cas", [2, 3]),
            invoke_op(3, "read", None), ok_op(3, "read", 3)))

    def test_mutex(self):
        assert not both(m.mutex(), History.of(
            invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
            invoke_op(1, "acquire", None), ok_op(1, "acquire", None)))

    def test_unsupported_model_unknown(self):
        p = prepare.prepare(m.noop, History.of(
            invoke_op(0, "add", 1), ok_op(0, "add", 1)))
        assert dense.check_packed(p)["valid?"] == "unknown"

    def test_wide_window_unknown(self):
        h = synth.generate_register_history(
            80, concurrency=dense.MAX_DENSE_WINDOW + 3, seed=2)
        p = prepare.prepare(m.cas_register(), h)
        if p.window > dense.MAX_DENSE_WINDOW:
            assert dense.check_packed(p)["valid?"] == "unknown"

    def test_plan_buckets(self):
        h = synth.generate_register_history(30, concurrency=5, seed=1,
                                            value_range=3)
        p = prepare.prepare(m.cas_register(), h)
        pl = dense.plan(p)
        assert pl is not None
        w, ns, nil_id, init_id = pl
        assert w >= p.window and w in dense._W_BUCKETS
        assert ns >= nil_id + 1 and ns in dense._NS_BUCKETS
        assert init_id == nil_id  # register starts nil


@pytest.mark.parametrize("seed", range(15))
def test_register_parity_valid(seed):
    h = synth.generate_register_history(40, concurrency=4, seed=seed,
                                        value_range=3, crash_prob=0.15)
    assert both(m.cas_register(), h) is True


@pytest.mark.parametrize("seed", range(15))
def test_register_parity_corrupted(seed):
    h = synth.generate_register_history(40, concurrency=4, seed=seed,
                                        value_range=3, crash_prob=0.1)
    h = synth.corrupt_history(h, seed=seed)
    both(m.cas_register(), h)


@pytest.mark.parametrize("seed", range(10))
def test_mutex_parity(seed):
    h = synth.generate_mutex_history(40, concurrency=4, seed=seed,
                                     crash_prob=0.15)
    assert both(m.mutex(), h) is True


@pytest.mark.parametrize("seed", range(6))
def test_many_crashes_wide_window(seed):
    # The flagship shape: live concurrency + accumulated crashed slots.
    h = synth.generate_register_history(300, concurrency=4, seed=seed,
                                        value_range=4, crash_prob=0.05,
                                        max_crashes=10)
    p = prepare.prepare(m.cas_register(), h)
    assert p.window > 4  # crashes actually widened the window
    assert both(m.cas_register(), h) is True


@pytest.mark.parametrize("seed", range(6))
def test_many_crashes_corrupted(seed):
    h = synth.generate_register_history(300, concurrency=4, seed=seed,
                                        value_range=4, crash_prob=0.05,
                                        max_crashes=10)
    both(m.cas_register(), synth.corrupt_history(h, seed=seed))


def test_chunk_boundary_carry():
    # Tiny chunks force the frontier to carry across many dispatches.
    h = synth.generate_register_history(120, concurrency=4, seed=9,
                                        crash_prob=0.1)
    assert both(m.cas_register(), h, chunk=8) is True
    bad = synth.corrupt_history(h, seed=9)
    both(m.cas_register(), bad, chunk=8)


def test_snapshots_decode_matches_oracle_frontier():
    # The entry-bitmap snapshot at base 0 holds exactly the init config.
    h = synth.generate_register_history(60, concurrency=4, seed=4,
                                        crash_prob=0.1)
    p = prepare.prepare(m.cas_register(), h)
    snaps = []
    dense.check_packed(p, chunk=16, snapshots=snaps)
    assert snaps[0][0] == 0
    w, ns, nil_id, init_id = dense.plan(p)
    cfgs = dense.decode_bitmap(snaps[0][1], nil_id)
    assert cfgs == [(0, (int(np.int32(-(2 ** 31))),))] or \
        cfgs == [(0, (init_id,))]
    assert [b for b, _ in snaps] == list(range(0, p.R, 16))


@pytest.mark.parametrize("seed", range(8))
def test_pallas_backend_parity(seed):
    # The pallas chunk kernel (interpreted off-TPU) must agree with the
    # oracle on valid, corrupted, and crash-heavy histories.
    h = synth.generate_register_history(80, concurrency=4, seed=seed,
                                        value_range=3, crash_prob=0.1,
                                        max_crashes=6)
    if seed % 2:
        h = synth.corrupt_history(h, seed=seed)
    p = prepare.prepare(m.cas_register(), h)
    want = cpu.check_packed(p)["valid?"]
    r = dense.check_packed(p, backend="pallas")
    assert r["valid?"] == want, f"pallas={r} cpu={want}"


def _pallas_matches_xla(p, chunk=dense.CHUNK):
    # Verdict, dead row and named op equal the XLA backend's.
    rx = dense.check_packed(p, backend="xla", chunk=chunk)
    rp = dense.check_packed(p, backend="pallas", chunk=chunk)
    assert rp["valid?"] is rx["valid?"]
    assert rp.get("dead-row") == rx.get("dead-row")
    assert rp.get("op") == rx.get("op")
    return rp


def _register_400():
    h = synth.generate_register_history(400, concurrency=4, seed=3,
                                        crash_prob=0.02)
    return prepare.prepare(m.cas_register(), h)


@pytest.mark.parametrize("model,n_ops,crash_prob,chunk", [
    ("mutex", 60, 0.1, 16),              # 16-row chunks and blocks
    ("mutex", 300, 0.02, dense.CHUNK),   # one chunk under one block
    ("cas_register", 400, 0.02, 200),    # 200-row chunks: padded blocks
])
def test_pallas_chunk_boundary_and_mutex(model, n_ops, crash_prob, chunk):
    if model == "mutex":
        h = synth.generate_mutex_history(n_ops, concurrency=4, seed=3,
                                         crash_prob=crash_prob)
        p = prepare.prepare(m.mutex(), h)
    else:
        p = _register_400()
    assert p.R % chunk % dense_pallas.block_rows(chunk) != 0
    assert dense.plan(p)[0] <= 10
    want = cpu.check_packed(p)["valid?"]
    assert _pallas_matches_xla(p, chunk)["valid?"] == want


def _dead_at(row):
    """A 300-op register history whose frontier dies at return row
    ``row`` (negative: from the end): the first seed's whose row ``row``
    returns an ok read, with that read's value changed to one no op
    writes. Returns the pack and the row."""
    for seed in range(64):
        h = synth.generate_register_history(300, concurrency=4, seed=seed,
                                            value_range=3, crash_prob=0.05,
                                            max_crashes=4)
        p = prepare.prepare(m.cas_register(), h)
        r = row % p.R
        o = p.ops[int(p.ret_op[r])]
        if o.f == "read":
            ev = list(h)
            ev[o.return_pos] = ev[o.return_pos].replace(
                value=(o.value or 0) + 1000)
            return prepare.prepare(m.cas_register(), History(ev)), r
    raise AssertionError(f"no seed returns a read at row {row}")


_RB = dense_pallas.ROW_BLOCK


@pytest.mark.parametrize("row", [0, _RB - 1, _RB, _RB + _RB // 2, -1],
                         ids=["first", "block-end", "block-start",
                              "mid-block", "last"])
def test_pallas_dead_row_matches_xla(row):
    # The frontier dies at the first and last row of a block, mid-block
    # and at the history's last row: the blocked sweep stops there.
    p, r = _dead_at(row)
    rp = _pallas_matches_xla(p)
    assert rp["valid?"] is False and rp["dead-row"] == r


def test_pallas_grid_steps_count_rows_per_block():
    # A valid verdict counts the grid steps that swept rows: each swept
    # ROW_BLOCK rows but the last, which swept the remainder.
    p = _register_400()
    with obs_trace.request() as req:
        r = dense.check_packed(p, backend="pallas")
    counts = req.record()["counts"]
    steps, rows = counts["dense.grid_steps"], counts["dense.rows"]
    assert r["valid?"] is True and rows == p.R
    assert (steps - 1) * _RB < rows <= steps * _RB


@pytest.mark.parametrize("seed", range(4))
def test_pallas_wide_window_parity(seed):
    # Windows 10-16 put the returner's slot on the SUBLANE axis of a
    # multi-row bitmap (w >= 12), the return filter's dynamic sublane
    # roll. Verdict must equal the oracle's, dead row the XLA path's.
    h = synth.generate_register_history(300, concurrency=8, seed=seed,
                                        value_range=3, crash_prob=0.05,
                                        max_crashes=8)
    if seed % 2:
        h = synth.corrupt_history(h, seed=seed)
    p = prepare.prepare(m.cas_register(), h)
    assert dense.plan(p)[0] >= 10
    want = cpu.check_packed(p)["valid?"]
    rx = dense.check_packed(p, backend="xla")
    rp = dense.check_packed(p, backend="pallas")
    assert rp["valid?"] == rx["valid?"] == want
    assert rp.get("dead-row") == rx.get("dead-row")
