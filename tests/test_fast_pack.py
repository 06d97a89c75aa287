"""Bit-parity fuzz: the vectorized packing pipeline (JEPSEN_TPU_FAST_PACK,
lin/prepare.py) vs the Python spec loops — every PACKED_STATE_KERNELS
family, crashed ops, :info completions, error parity, and the
reduction_tables chain core on the same corpora (ISSUE 16 tentpole a)."""

import dataclasses
import operator
import threading

import numpy as np
import pytest

from jepsen_tpu import models as m
from jepsen_tpu.history import History, invoke_op, ok_op, info_op, fail_op
from jepsen_tpu.lin import prepare, synth
from jepsen_tpu.lin.prepare import UnsupportedHistory
from jepsen_tpu.lin.supervise import history_fingerprint
from jepsen_tpu.obs import trace

# Quick tier: no XLA compiles (make test-quick / pytest -m quick).
pytestmark = pytest.mark.quick


def _pack_one(model, h, monkeypatch, fast):
    monkeypatch.setenv("JEPSEN_TPU_FAST_PACK", "1" if fast else "0")
    try:
        p = prepare.prepare(model, h)
    except UnsupportedHistory as e:
        return ("error", str(e), getattr(e, "kind", None))
    red = prepare.reduction_tables(p)
    return ("ok", p, red)


def _assert_parity(model, h, monkeypatch):
    """prepare() + reduction_tables() under FAST_PACK=1 vs =0 must be
    bit-identical: same tables, interns, ops, fingerprint, reduction
    tables — or the same error."""
    fast = _pack_one(model, h, monkeypatch, True)
    spec = _pack_one(model, h, monkeypatch, False)
    assert fast[0] == spec[0], (fast, spec)
    if fast[0] == "error":
        assert fast[1:] == spec[1:]
        return None
    a, ra = fast[1], fast[2]
    b, rb = spec[1], spec[2]
    assert a.window == b.window and a.R == b.R
    for name in ("ret_slot", "ret_op", "active", "slot_f", "slot_v",
                 "slot_op", "crashed", "init_state"):
        va, vb = getattr(a, name), getattr(b, name)
        assert np.asarray(va).dtype == np.asarray(vb).dtype, name
        np.testing.assert_array_equal(va, vb, err_msg=name)
    assert (a.kernel.name if a.kernel else None) == \
        (b.kernel.name if b.kernel else None)
    assert a.intern == b.intern
    assert a.unintern == b.unintern
    assert a.ops == b.ops                       # LinOp dataclass equality
    assert a.crashed_ops == b.crashed_ops
    assert all(c is a.ops[i] for c, i in zip(a.crashed_ops, a.crashed_idx))
    assert history_fingerprint(a) == history_fingerprint(b)
    np.testing.assert_array_equal(ra[0], rb[0], err_msg="pure")
    np.testing.assert_array_equal(ra[1], rb[1], err_msg="pred")
    return a


# --- fuzz across kernel families --------------------------------------------

# The parity corpora: name -> (model, history of a seed, seeds).
CORPORA = {
    "register_crash_mix": (m.cas_register, lambda seed:
                           synth.generate_register_history(
                               1500, concurrency=7, seed=seed,
                               crash_prob=0.02, max_crashes=9), 6),
    "partitioned_cas": (m.cas_register, lambda seed:
                        synth.generate_partitioned_register_history(
                            3000, seed=seed, max_crashes=12,
                            invoke_bias=0.5), 4),
    "register_model": (m.register, lambda seed:
                       synth.generate_register_history(
                           800, concurrency=5, seed=seed, crash_prob=0.01,
                           max_crashes=4), 3),
    "mutex": (m.mutex, lambda seed: synth.generate_mutex_history(
        600, concurrency=5, seed=seed, crash_prob=0.02, max_crashes=6), 3),
    # Set histories take the spec _kernelize (vec form covers the
    # register/mutex band only) but still the vectorized pair + walk.
    "set": (m.set_model, lambda seed: synth.generate_set_history(
        400, concurrency=3, seed=seed), 3),
    "queue": (m.fifo_queue, lambda seed: synth.generate_queue_history(
        500, concurrency=3, seed=seed, crash_prob=0.02, max_crashes=4), 3),
}
CORPUS_SEEDS = [(name, seed) for name, (_, _, n) in CORPORA.items()
                for seed in range(n)]


def _corpus_parity(name, seed, monkeypatch):
    model, gen, _ = CORPORA[name]
    return _assert_parity(model(), gen(seed), monkeypatch)


@pytest.mark.parametrize("seed", range(6))
def test_parity_register_crash_mix(seed, monkeypatch):
    _corpus_parity("register_crash_mix", seed, monkeypatch)


@pytest.mark.parametrize("seed", range(4))
def test_parity_partitioned_cas(seed, monkeypatch):
    p = _corpus_parity("partitioned_cas", seed, monkeypatch)
    assert p is not None and len(p.crashed_ops) > 0


@pytest.mark.parametrize("seed", range(3))
def test_parity_register_model(seed, monkeypatch):
    _corpus_parity("register_model", seed, monkeypatch)


@pytest.mark.parametrize("seed", range(3))
def test_parity_mutex(seed, monkeypatch):
    _corpus_parity("mutex", seed, monkeypatch)


@pytest.mark.parametrize("seed", range(3))
def test_parity_set_spec_kernelize_fallback(seed, monkeypatch):
    _corpus_parity("set", seed, monkeypatch)


@pytest.mark.parametrize("seed", range(3))
def test_parity_queue(seed, monkeypatch):
    _corpus_parity("queue", seed, monkeypatch)


# --- the lazy op sequence ----------------------------------------------------


@pytest.mark.parametrize("name,seed", CORPUS_SEEDS)
def test_lazy_ops_equal_spec_list(name, seed):
    # The fast pairing's LinOpSeq against pair_ops' list, element for
    # element: len, index (both signs), slice, iteration, == both ways,
    # and the crashed ops the ok column selects.
    h = list(CORPORA[name][1](seed))
    spec = prepare.pair_ops(h)
    lazy, invoke_pos, return_pos, ok = prepare._pair_ops_vec_arrays(h)
    n = len(spec)
    assert len(lazy) == n > 0
    with trace.request() as req:
        for k in (0, n // 2, -1):
            assert lazy[k] == spec[k] and lazy[k] is lazy[k]
        assert req.counts["prepare.ops_built"] == 3
        assert lazy[3:n:7] == spec[3:n:7] and lazy[::-1] == spec[::-1]
        assert list(lazy) == spec and lazy == spec and spec == lazy
        assert req.counts["prepare.ops_built"] == n     # each built once
    with pytest.raises(IndexError):
        lazy[n]
    assert lazy == prepare._pair_ops_vec_arrays(h)[0]   # view == view
    altered = spec[:-1] + [dataclasses.replace(spec[-1], value="x")]
    assert lazy != altered and altered != lazy and lazy != spec[:-1]
    np.testing.assert_array_equal(invoke_pos, [o.invoke_pos for o in spec])
    np.testing.assert_array_equal(
        return_pos, [-1 if o.return_pos is None else o.return_pos
                     for o in spec])
    np.testing.assert_array_equal(ok, [o.ok for o in spec])
    assert [lazy[i] for i in np.flatnonzero(~ok).tolist()] == \
        [o for o in spec if o.return_pos is None]


def test_lazy_ops_racing_readers_share_one_build():
    # The competition's threads share one pack: readers that race on the
    # same ops get the same objects, each built once.
    lazy = prepare._pair_ops_vec_arrays(list(_crashy_history()))[0]
    with trace.request() as req:
        lazy._build([4, 7])
        first = lazy[7]
        lazy._build([7, 9])
        assert lazy[7] is first
        assert req.counts["prepare.ops_built"] == 3
    seen = []
    threads = [threading.Thread(target=lambda: seen.append(list(lazy)))
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(seen) == 4 and len(lazy.cols.built) == len(lazy)
    assert all(map(operator.is_, seen[0], lazy))
    assert all(all(map(operator.is_, s, seen[0])) for s in seen[1:])


def _verdict(h, fast: bool, monkeypatch):
    from jepsen_tpu import checker

    monkeypatch.setenv("JEPSEN_TPU_FAST_PACK", "1" if fast else "0")
    return checker.linearizable(algorithm="tpu").check(
        None, m.cas_register(), h, {})


def _crashy_history():
    return synth.generate_register_history(
        600, concurrency=4, seed=3, value_range=3, crash_prob=0.05,
        max_crashes=6)


@pytest.mark.compiles
def test_valid_dense_verdict_builds_no_ops(monkeypatch):
    # A valid history on the dense path reads none of its ops, its
    # crashed ones included: the verdict's counter reads 0.
    h = _crashy_history()
    r = _verdict(h, True, monkeypatch)
    assert r["valid?"] is True and r["analyzer"] == "tpu-dense"
    assert r["counts"]["prepare.ops_built"] == 0
    assert len(prepare.prepare(m.cas_register(), h).crashed_ops) > 0


@pytest.mark.compiles
@pytest.mark.parametrize("seed", [3, 5])
def test_invalid_verdict_same_from_lazy_and_spec_ops(seed, monkeypatch):
    # The counterexample built from the few ops it reads equals the one
    # the spec pairing's eager list gives.
    h = synth.corrupt_history(_crashy_history(), seed=seed)
    a = _verdict(h, True, monkeypatch)
    b = _verdict(h, False, monkeypatch)
    assert a["valid?"] is b["valid?"] is False
    for k in ("op", "configs", "final-paths", "dead-row"):
        assert a[k] == b[k], k
    n = len(prepare.pair_ops(list(h)))
    assert b["counts"]["prepare.ops_built"] == n
    assert 0 < a["counts"]["prepare.ops_built"] < n


# --- edge cases --------------------------------------------------------------


def test_parity_empty_and_trivial(monkeypatch):
    _assert_parity(m.cas_register(), History.of(), monkeypatch)
    _assert_parity(m.cas_register(), History.of(
        invoke_op(0, "write", 5), ok_op(0, "write", 5)), monkeypatch)


def test_parity_info_fail_nemesis_mix(monkeypatch):
    h = History.of(
        invoke_op("nemesis", "start", None),
        invoke_op(0, "write", 1),
        invoke_op(1, "read", None),
        ok_op(0, "write", 1),
        info_op(1, "read", None),          # crashed read: elided
        invoke_op(2, "cas", [1, 2]),
        invoke_op(3, "write", 7),
        fail_op(3, "write", 7),            # failed: dropped entirely
        ok_op(2, "cas", [1, 2]),
        invoke_op(0, "read", None),
        ok_op(0, "read", 2),
        invoke_op(1, "write", 9),          # dangling: crashed
        invoke_op("nemesis", "stop", None),
    )
    p = _assert_parity(m.cas_register(), h, monkeypatch)
    assert len(p.crashed_ops) == 1 and p.crashed_ops[0].value == 9


def test_parity_double_invoke_error(monkeypatch):
    h = History.of(
        invoke_op(0, "write", 1),
        invoke_op(0, "write", 2),
        ok_op(0, "write", 2),
    )
    _assert_parity(m.cas_register(), h, monkeypatch)


def test_parity_window_overflow_error(monkeypatch):
    ops = [invoke_op(i, "write", i) for i in range(70)]
    ops += [ok_op(i, "write", i) for i in range(70)]
    h = History.of(*ops)
    fast = _pack_one(m.cas_register(), h, monkeypatch, True)
    spec = _pack_one(m.cas_register(), h, monkeypatch, False)
    assert fast[0] == spec[0] == "error"
    assert fast[1:] == spec[1:]
    assert fast[2] == "window"


def test_parity_cas_bad_pair_error(monkeypatch):
    h = History.of(invoke_op(0, "cas", 7), ok_op(0, "cas", 7))
    fast = _pack_one(m.cas_register(), h, monkeypatch, True)
    spec = _pack_one(m.cas_register(), h, monkeypatch, False)
    assert fast == spec and fast[0] == "error"


@pytest.mark.parametrize("vals", [
    ("a", "b", "c"),                       # strings
    (True, False, 1),                      # bools must not silently be ints
    (1 << 62, -(1 << 62) - 1, 3),          # large ints in int64
    (1 << 64, -(1 << 63) - 1, 3),          # past int64
    (1.5, 2.5, 1.5),                       # floats
])
def test_parity_non_int_value_domains(vals, monkeypatch):
    # The vec interner covers the plain-int domain; anything else must
    # fall back to the spec interner per call — and stay bit-identical.
    h = History.of(
        invoke_op(0, "write", vals[0]), ok_op(0, "write", vals[0]),
        invoke_op(1, "write", vals[1]), ok_op(1, "write", vals[1]),
        invoke_op(0, "read", None), ok_op(0, "read", vals[2]),
    )
    p = _assert_parity(m.cas_register(), h, monkeypatch)
    assert p is not None


@pytest.mark.parametrize("procs", [
    (0, 70000, 3),                         # far-apart ints
    (1 << 70, -(1 << 70), 2),              # ints past int64
    (1, 1.5, "a"),                         # mixed types: factorized
    (True, 2, 3),                          # a bool is no plain int
])
def test_parity_process_domains(procs, monkeypatch):
    # The fast pairing factorizes processes of any kind into sort
    # keys, grouping events as the spec's pending dict does.
    a, b, c = procs
    h = History.of(
        invoke_op(a, "write", 1), invoke_op(b, "write", 2),
        ok_op(a, "write", 1), invoke_op(c, "read", None),
        ok_op(b, "write", 2), ok_op(c, "read", 2),
        invoke_op(a, "cas", [2, 3]), info_op(a, "cas", [2, 3]),
        invoke_op(b, "read", None),
    )
    p = _assert_parity(m.cas_register(), h, monkeypatch)
    assert [o.process for o in p.ops] == [a, b, c, a]


def test_parity_dequeue_value_semantics(monkeypatch):
    h = History.of(
        invoke_op(0, "enqueue", 4), ok_op(0, "enqueue", 4),
        invoke_op(1, "dequeue", None), ok_op(1, "dequeue", 4),
        invoke_op(0, "enqueue", 5), ok_op(0, "enqueue", 5),
        invoke_op(1, "dequeue", None), info_op(1, "dequeue", None),
    )
    _assert_parity(m.fifo_queue(), h, monkeypatch)


# --- incremental packer: vectorized settle vs the spec loop ------------------


def _stream_pack(model, events, step, monkeypatch, fast,
                 flip_at=None):
    """Feed/settle in `step`-sized chunks; `flip_at` flips the packer
    mode at that chunk (exercises the spec->vec backfill)."""
    from jepsen_tpu.stream import IncrementalPacker

    monkeypatch.setenv("JEPSEN_TPU_FAST_PACK", "1" if fast else "0")
    pk = IncrementalPacker(model)
    fps = []
    for ci, i in enumerate(range(0, len(events), step)):
        if flip_at is not None and ci == flip_at:
            monkeypatch.setenv("JEPSEN_TPU_FAST_PACK",
                               "0" if fast else "1")
        pk.feed_many(events[i:i + step])
        pk.settle()
        fps.append(pk.prefix_fingerprint(pk.R))
    pk.settle(final=True)
    fps.append(pk.prefix_fingerprint(pk.R))
    return pk, fps


def _assert_stream_parity(model, events, step, monkeypatch,
                          flip_at=None):
    a, fa = _stream_pack(model, list(events), step, monkeypatch, True,
                         flip_at)
    b, fb = _stream_pack(model, list(events), step, monkeypatch, False)
    assert fa == fb                       # per-increment fingerprints
    pa, pb = a.packed(), b.packed()
    assert pa.window == pb.window and pa.R == pb.R
    for name in ("ret_slot", "ret_op", "active", "slot_f", "slot_v",
                 "slot_op", "crashed"):
        va, vb = getattr(pa, name), getattr(pb, name)
        assert np.asarray(va).dtype == np.asarray(vb).dtype, name
        np.testing.assert_array_equal(va, vb, err_msg=name)
    assert pa.intern == pb.intern and pa.unintern == pb.unintern
    assert a.ops == b.ops
    np.testing.assert_array_equal(pa._reduction_tables[0],
                                  pb._reduction_tables[0])
    np.testing.assert_array_equal(pa._reduction_tables[1],
                                  pb._reduction_tables[1])
    assert a.max_used == b.max_used and a._free == b._free
    assert a._slot_of == b._slot_of and a._cur_active == b._cur_active


@pytest.mark.parametrize("seed,step", [(0, 17), (1, 50), (2, 1),
                                       (3, 999), (4, 7)])
def test_stream_settle_parity(seed, step, monkeypatch):
    h = synth.generate_register_history(
        900, concurrency=8, seed=seed, crash_prob=0.03, max_crashes=7)
    _assert_stream_parity(m.cas_register(), h, step, monkeypatch)


@pytest.mark.parametrize("seed", range(2))
def test_stream_settle_parity_mutex(seed, monkeypatch):
    h = synth.generate_mutex_history(
        400, concurrency=6, seed=seed, crash_prob=0.03, max_crashes=5)
    _assert_stream_parity(m.mutex(), h, 23, monkeypatch)


def test_stream_settle_parity_mode_flip(monkeypatch):
    # Flip FAST_PACK mid-stream: the vec settle backfills the growing
    # per-op arrays from the spec-walked prefix and stays bit-exact.
    h = synth.generate_register_history(
        600, concurrency=7, seed=11, crash_prob=0.02, max_crashes=5)
    _assert_stream_parity(m.cas_register(), h, 41, monkeypatch,
                          flip_at=5)
    _assert_stream_parity(m.cas_register(), h, 41, monkeypatch,
                          flip_at=2)


def test_stream_settle_vs_oneshot(monkeypatch):
    # Vec incremental vs vec one-shot: the cross-check test_stream.py
    # runs at default mode, pinned here explicitly.
    monkeypatch.setenv("JEPSEN_TPU_FAST_PACK", "1")
    from jepsen_tpu.stream import IncrementalPacker

    h = list(synth.generate_register_history(
        700, concurrency=8, seed=3, crash_prob=0.04, max_crashes=6))
    one = prepare.prepare(m.cas_register(), list(h))
    r1 = prepare.reduction_tables(one)
    pk = IncrementalPacker(m.cas_register())
    for i in range(0, len(h), 29):
        pk.feed_many(h[i:i + 29])
        pk.settle()
    pk.settle(final=True)
    p2 = pk.packed()
    assert p2.R == one.R and p2.window == one.window
    for name in ("ret_slot", "ret_op", "active", "slot_f", "slot_v",
                 "slot_op", "crashed"):
        np.testing.assert_array_equal(
            getattr(one, name), getattr(p2, name), err_msg=name)
    np.testing.assert_array_equal(r1[1], p2._reduction_tables[1])


def test_fast_pack_stats_and_mode(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_FAST_PACK", "1")
    prepare.reset_pack_stats()
    h = synth.generate_register_history(300, concurrency=4, seed=0)
    p = prepare.prepare(m.cas_register(), h)
    prepare.reduction_tables(p)
    st = prepare.pack_stats()
    assert st["mode"] == "vec"
    assert st["prepare_calls"] == 1 and st["reduction_calls"] == 1
    assert st["prepare_s"] > 0.0 and st["reduction_s"] >= 0.0
    prepare.reset_pack_stats()
    assert prepare.pack_stats()["prepare_calls"] == 0
