"""Host-side history packing for the linearizability search.

Converts a Jepsen-style history (vector of invoke/ok/fail/info op maps,
reference core.clj:143-217) into the dense int-array form both the CPU
reference checker and the TPU BFS kernel consume:

1. **Pairing** — each invocation is matched with the next completion by the
   same process. ``fail`` ops are removed entirely (a failed op definitely
   did not happen); ``info`` ops (crashed/indeterminate, produced by the
   runner at core.clj:185-217) stay concurrent with everything after them
   and may be linearized at any later point, or never.
2. **Crashed-read elision** — an unobserved read with no return can always
   be linearized (it never changes state), so crashed reads are dropped.
3. **Slot assignment** — the linearized-op bitset only needs bits for ops
   whose linearized-status varies across frontier configs: exactly the
   *pending* ops. Slots are recycled when an op returns (its bit is then 1
   in every surviving config and is cleared for reuse), so the bitset width
   is the max concurrency window, not the history length. This is the key
   compression that keeps 100k-op histories in a 32/64-bit bitset.
4. **Value interning** — op values (arbitrary hashables) become dense int32
   ids shared with model states, so the device kernel only ever compares
   ints. ``None`` maps to the NIL sentinel (a read invoked with nil matches
   any state, model.clj:31-32).
5. **Return-event table** — the frontier only changes at completion events,
   so the search iterates over R = #ok-ops rows, each carrying the
   returning slot plus the snapshot of active slots with their (f, value).
"""

from __future__ import annotations

import operator
import os
import threading
import time
from collections.abc import Sequence
from dataclasses import dataclass, field
from itertools import repeat
from operator import attrgetter, itemgetter
from typing import Any, Callable

import numpy as np

from jepsen_tpu import models as model_ns
from jepsen_tpu.history import INFO, INVOKE, OK, Op
from jepsen_tpu.history import FAIL as H_FAIL
from jepsen_tpu.models import kernels as K
from jepsen_tpu.models.kernels import (F_IDS, NIL, VALUE_WIDTH, KernelModel,
                                       kernel_for)
from jepsen_tpu.obs import trace as obs_trace


def fast_pack_enabled() -> bool:
    """``JEPSEN_TPU_FAST_PACK``: the vectorized packer (sort/searchsorted/
    cumsum numpy passes, bit-identical to the Python walk). Default on;
    ``=0`` falls back to the Python spec walk, which stays the executable
    reference. Re-read per call (the env-knob convention, doc/env.md)."""
    return os.environ.get("JEPSEN_TPU_FAST_PACK", "") != "0"


# Pack-wall accounting (bench's pack rung + the service's pack-seconds
# counter read this; obs/trace spans carry the per-call attribution).
_pack_stats = {"prepare_s": 0.0, "prepare_calls": 0,
               "reduction_s": 0.0, "reduction_calls": 0,
               "incr_s": 0.0, "incr_calls": 0, "mode": ""}


def pack_stats() -> dict:
    """Snapshot of cumulative packing wall this process (seconds)."""
    return dict(_pack_stats)


def reset_pack_stats() -> None:
    for k in _pack_stats:
        _pack_stats[k] = "" if k == "mode" else (0.0 if k.endswith("_s")
                                                 else 0)


class UnsupportedHistory(Exception):
    """Raised when a history cannot be packed (unknown f, window overflow
    beyond the configured maximum, un-internable values).

    ``kind`` is a stable machine-readable tag ("window" for concurrency-
    window overflow, "other" otherwise) — callers branch on it, never on
    the message text (jepsen_tpu.lin.analysis routes window overflows to
    the unbounded host search)."""

    def __init__(self, message: str, kind: str = "other"):
        super().__init__(message)
        self.kind = kind


@dataclass
class LinOp:
    """One logical operation (invocation + optional completion)."""

    op_index: int           # index of the invocation in the history
    process: Any
    f: str
    value: Any              # semantic value: completion value for ok reads
    ok: bool                # True if completed ok; False if crashed (info)
    invoke_pos: int         # position of invocation event
    return_pos: int | None  # position of ok completion event, None if crashed


@dataclass
class PackedHistory:
    """Dense arrays driving the frontier search; see module docstring."""

    model: Any                   # the Python model (semantic reference)
    kernel: KernelModel | None   # device kernel, None if model unsupported
    ops: Sequence[LinOp]         # logical ops (reporting / witnesses)
    window: int                  # W = bitset width in use
    R: int                       # number of return events
    ret_slot: np.ndarray         # i32[R]   slot of the returning op
    ret_op: np.ndarray           # i32[R]   index into ops of the returner
    active: np.ndarray           # bool[R,W] slots invoked & unreturned
    slot_f: np.ndarray           # i32[R,W] function id per active slot
    slot_v: np.ndarray           # i32[R,W,VALUE_WIDTH] interned values
    slot_op: np.ndarray          # i32[R,W] index into ops per active slot
    crashed: np.ndarray          # bool[R,W] active slot holds a crashed op
    init_state: np.ndarray       # i32[S]
    intern: dict                 # value -> id
    unintern: list               # id -> value
    crashed_idx: list            # indices into ops of the crashed ops

    @property
    def state_width(self) -> int:
        return len(self.init_state)

    @property
    def crashed_ops(self) -> list[LinOp]:
        """The info ops pending at the end (never linearized), in
        invocation order; built when read."""
        return [self.ops[i] for i in self.crashed_idx]


MAX_WINDOW = 64


def _semantic_value(f: str, invoke: Op, completion: Op | None) -> Any:
    """The value the model checks: reads and dequeues are checked against
    what they *observed* (the completion's value, knossos.history/complete
    semantics); mutations against what they *requested* (the invocation's
    value)."""
    if f == "read":
        return completion.value if (completion is not None
                                    and completion.is_ok) else None
    if f == "dequeue" and completion is not None and completion.is_ok \
            and completion.value is not None:
        return completion.value
    return invoke.value


def pair_ops(history: list[Op]) -> list[LinOp]:
    """Match invocations with completions; drop failed ops and crashed
    reads. Dangling invocations at the end of history count as crashed
    (the runner emits :info for those, core.clj:185-217)."""
    ops: list[LinOp] = []
    pending: dict[Any, tuple[int, Op]] = {}
    for pos, op in enumerate(history):
        if op.process == "nemesis" or op.f in ("start", "stop"):
            continue
        if op.is_invoke:
            if op.process in pending:
                raise UnsupportedHistory(
                    f"process {op.process} invoked twice without completing "
                    f"(positions {pending[op.process][0]} and {pos})")
            pending[op.process] = (pos, op)
        elif op.process in pending:
            ipos, inv = pending.pop(op.process)
            if op.is_fail:
                continue  # failed ops definitely did not happen
            ok = op.is_ok
            ops.append(LinOp(
                op_index=inv.index if inv.index is not None else ipos,
                process=op.process, f=inv.f,
                value=_semantic_value(inv.f, inv, op),
                ok=ok, invoke_pos=ipos,
                return_pos=pos if ok else None))
    # Dangling invokes = crashed.
    for proc, (ipos, inv) in pending.items():
        ops.append(LinOp(
            op_index=inv.index if inv.index is not None else ipos,
            process=proc, f=inv.f,
            value=_semantic_value(inv.f, inv, None),
            ok=False, invoke_pos=ipos, return_pos=None))
    # Crashed reads never constrain anything: elide.
    ops = [o for o in ops if o.ok or o.f != "read"]
    ops.sort(key=lambda o: o.invoke_pos)
    return ops


@dataclass
class OpColumns:
    """The fast pairing's per-op columns, in invocation order: what each
    :class:`LinOp` holds, as arrays over ``history`` (the events the
    positions index). ``built`` caches the LinOps read so far, shared by
    every :class:`LinOpSeq` over these columns; ``lock`` serializes the
    builds of readers in different threads."""

    history: list
    invoke_pos: np.ndarray       # i64[n]
    return_pos: np.ndarray       # i64[n]  (-1 = crashed)
    ok: np.ndarray               # bool[n]
    f_code: np.ndarray           # [n]  index into f_names
    f_names: list                # distinct fs, first-occurrence order
    value: np.ndarray            # object[n]  semantic value
    built: dict = field(default_factory=dict)    # op id -> LinOp
    lock: Any = field(default_factory=threading.Lock)


class LinOpSeq(Sequence):
    """Read-only sequence of LinOps over :class:`OpColumns`. An op is
    built on first read and cached, so identity is stable and a verdict
    that names a few ops builds only those;
    ``trace.count("prepare.ops_built")`` counts the builds. Equal to a
    list (or another sequence of this kind) holding equal LinOps in the
    same order. A loop that reads every op many times should copy the
    sequence to a list once."""

    __slots__ = ("cols",)

    def __init__(self, cols: OpColumns):
        self.cols = cols

    def __len__(self) -> int:
        return len(self.cols.ok)

    def __getitem__(self, k):
        if isinstance(k, slice):
            return self._get_many(range(len(self))[k])
        n = len(self)
        k = operator.index(k)
        if k < 0:
            k += n
        if not 0 <= k < n:
            raise IndexError("LinOpSeq index out of range")
        built = self.cols.built
        if k not in built:
            self._build([k])
        return built[k]

    def __iter__(self):
        return iter(self._get_many(range(len(self))))

    def __eq__(self, other):
        if not isinstance(other, (LinOpSeq, list)):
            return NotImplemented
        return len(self) == len(other) and \
            all(map(operator.eq, self, other))

    def __repr__(self) -> str:
        return (f"LinOpSeq({len(self)} ops, "
                f"{len(self.cols.built)} built)")

    def _get_many(self, ids) -> list:
        built = self.cols.built
        missing = [i for i in ids if i not in built] if built else ids
        if missing:
            self._build(missing)
        return list(map(built.__getitem__, ids))

    def _build(self, ids) -> None:
        """Build and cache the ops ``ids`` that no reader has built."""
        c = self.cols
        H = c.history
        new = LinOp.__new__
        with c.lock:
            if c.built:
                ids = [i for i in ids if i not in c.built]
            ix = np.arange(ids.start, ids.stop, ids.step) \
                if isinstance(ids, range) else np.array(ids, np.int64)
            out = []
            app = out.append
            for ip, rp, ok, value in zip(
                    c.invoke_pos[ix].tolist(), c.return_pos[ix].tolist(),
                    c.ok[ix].tolist(), c.value[ix].tolist()):
                inv = H[ip]
                o = new(LinOp)
                o.__dict__ = {
                    "op_index": inv.index if inv.index is not None else ip,
                    "process": inv.process, "f": inv.f, "value": value,
                    "ok": ok, "invoke_pos": ip,
                    "return_pos": rp if ok else None}
                app(o)
            c.built.update(zip(ids, out))
        obs_trace.count("prepare.ops_built", len(out))


_TYPE_CODE = {INVOKE: 0, OK: 1, H_FAIL: 2, INFO: 3}
_get_type = attrgetter("type")
_get_f = attrgetter("f")
_get_process = attrgetter("process")
_get_value = attrgetter("value")


def _factorize(xs: list):
    """Dense ids of hashables, in first-occurrence order, in C-level
    passes: ``(ids[len(xs)], {x: id})``. The ids take the smallest
    unsigned dtype, which numpy's stable sort radix-sorts."""
    d = dict.fromkeys(xs)
    for i, k in enumerate(d):
        d[k] = i
    return (np.fromiter(map(d.__getitem__, xs),
                        np.min_scalar_type(len(d)), len(xs)), d)


def _empty_cols(history: list):
    z = np.zeros(0, np.int64)
    cols = OpColumns(history, z, z, np.zeros(0, bool), z, [],
                     np.zeros(0, object))
    return (LinOpSeq(cols), np.zeros(0, np.int32), np.zeros(0, np.int32),
            cols.ok)


def _pair_ops_vec_arrays(history: list[Op]):
    """Vectorized twin of :func:`pair_ops` (JEPSEN_TPU_FAST_PACK): the
    per-event pending-dict walk becomes a stable sort by (process, time)
    — within one process the relevant events alternate invoke/
    completion, so a completion pairs with its invocation exactly when
    the previous same-process event is an invoke, and an invoke
    following an invoke is the double-invoke error. Returns ``(ops,
    invoke_pos, return_pos, ok)``: ``ops`` a :class:`LinOpSeq` over the
    per-op columns, equal to the spec's list (same order, same ops,
    same errors) but building no LinOp until one is read; the
    position/ok columns as arrays (return_pos is -1 for crashed ops)."""
    n_ev = len(history)
    if n_ev == 0:
        return _empty_cols(history)
    tc = np.fromiter(map(_TYPE_CODE.get, map(_get_type, history),
                         repeat(4)), np.int8, n_ev)
    pids, pmap = _factorize(list(map(_get_process, history)))
    fids, fmap = _factorize(list(map(_get_f, history)))
    keep = np.ones(n_ev, bool)
    nem = pmap.get("nemesis")
    if nem is not None:
        keep &= pids != nem
    for excl in ("start", "stop"):
        fe = fmap.get(excl)
        if fe is not None:
            keep &= fids != fe
    idx = np.flatnonzero(keep)
    if idx.size == 0:
        return _empty_cols(history)
    # Group by process, time order within group (stable).
    g = idx[np.argsort(pids[idx], kind="stable")]
    pid_s = pids[g]
    tc_s = tc[g]
    first = np.empty(len(g), bool)
    first[0] = True
    first[1:] = pid_s[1:] != pid_s[:-1]
    prev_invoke = np.zeros(len(g), bool)
    prev_invoke[1:] = (tc_s[:-1] == 0) & ~first[1:]
    dbl = (tc_s == 0) & prev_invoke
    if dbl.any():
        j = np.flatnonzero(dbl)
        jj = j[np.argmin(g[j])]          # earliest second-invoke in history
        p1, p2 = int(g[jj - 1]), int(g[jj])
        raise UnsupportedHistory(
            f"process {history[p2].process} invoked twice without "
            f"completing (positions {p1} and {p2})")
    paired = (tc_s != 0) & prev_invoke & (tc_s != 2)   # fails drop
    last = np.empty(len(g), bool)
    last[-1] = True
    last[:-1] = first[1:]
    dangling = (tc_s == 0) & last                      # pending at end
    pj = np.flatnonzero(paired)
    ipos = np.concatenate([g[pj - 1], g[np.flatnonzero(dangling)]])
    cpos = np.concatenate([g[pj], np.full(int(dangling.sum()), -1,
                                          g.dtype)])
    okc = np.concatenate([tc_s[pj] == 1,               # OK completions
                          np.zeros(int(dangling.sum()), bool)])
    # Crashed reads constrain nothing: drop them.
    rf = fmap.get("read")
    if rf is not None:
        keep2 = okc | (fids[ipos] != rf)
        ipos, cpos, okc = ipos[keep2], cpos[keep2], okc[keep2]
    order = np.argsort(ipos, kind="stable")
    ipos, cpos, okc = ipos[order], cpos[order], okc[order]
    f_code = fids[ipos]

    def values_at(pos):
        return np.fromiter(map(_get_value, map(history.__getitem__,
                                               pos.tolist())),
                           object, len(pos))

    # _semantic_value's rules: the completion's value for reads (all ok
    # here) and for ok dequeues that saw one, else the invocation's.
    value = values_at(ipos)
    if rf is not None:
        rd = np.flatnonzero(f_code == rf)
        value[rd] = values_at(cpos[rd])
    df = fmap.get("dequeue")
    if df is not None:
        dq = np.flatnonzero((f_code == df) & okc)
        cv = values_at(cpos[dq])
        seen = np.fromiter(map(operator.is_not, cv, repeat(None)), bool,
                           len(dq))
        value[dq[seen]] = cv[seen]
    return_pos = np.where(okc, cpos, -1)
    cols = OpColumns(history, ipos, return_pos, okc, f_code, list(fmap),
                     value)
    return (LinOpSeq(cols), ipos.astype(np.int32),
            return_pos.astype(np.int32), okc)


class _Interner:
    def __init__(self):
        self.ids: dict = {}
        self.values: list = []

    def __call__(self, v) -> int:
        if v is None:
            return int(NIL)
        try:
            key = v
            hash(key)
        except TypeError:
            key = repr(v)
        if key not in self.ids:
            self.ids[key] = len(self.values)
            self.values.append(v)
        return self.ids[key]


def _op_f_and_values(o: LinOp, intern: _Interner) -> tuple[int, list[int]]:
    if o.f not in F_IDS:
        raise UnsupportedHistory(f"unknown op f={o.f!r} for device packing")
    f_id = F_IDS[o.f]
    v = [int(NIL)] * VALUE_WIDTH
    if o.f == "cas":
        if not isinstance(o.value, (list, tuple)) or len(o.value) != 2:
            raise UnsupportedHistory(f"cas value must be a pair: {o.value!r}")
        v[0] = intern(o.value[0])
        v[1] = intern(o.value[1])
    elif o.f in ("read", "write"):
        v[0] = intern(o.value)
    return f_id, v


# Device-formulation size bounds: histories past these fall back to the
# generic CPU search (kernel=None) rather than failing.
MAX_SET_WORDS = 16        # 16 x 31 = 496 distinct set elements
MAX_QUEUE_VALUES = 32     # distinct unordered-queue values (state width)
MAX_FIFO_CAP = 31         # fifo depth bound (state width 32)


def _max_queue_depth(ops: Sequence[LinOp], n_initial: int) -> int:
    """Upper bound on FIFO depth over every possible linearization: at any
    event position t, at most the enqueues *invoked* by t have linearized,
    and at least the ok dequeues *returned* by t have linearized."""
    events = []
    for o in ops:
        if o.f == "enqueue":
            events.append((o.invoke_pos, 1))
        elif o.f == "dequeue" and o.return_pos is not None:
            events.append((o.return_pos, -1))
    events.sort()
    depth = peak = n_initial
    for _, d in events:
        depth += d
        peak = max(peak, depth)
    return peak


def _no_kernel(n: int):
    return (None, np.array([0], np.int32), np.zeros(n, np.int32),
            np.full((n, VALUE_WIDTH), int(NIL), np.int32))


def _kernelize(model, ops: Sequence[LinOp], intern: _Interner):
    """Build the device kernel sized for this history plus the per-op
    interned (f, value-words) tables.

    Returns ``(kernel, init_state, op_f, op_v)``; kernel is None when the
    model — or this particular history — has no device formulation, in
    which case the generic CPU search takes over with exact semantics.
    The set/queue kernels are sized from the history (element count, value
    count, queue depth bound), so their packed-state width is data-driven.
    """
    n = len(ops)

    def tables(vw):
        return (np.zeros(n, np.int32),
                np.full((n, vw), int(NIL), np.int32))

    if isinstance(model, (model_ns.CASRegister, model_ns.Register,
                          model_ns.Mutex)):
        kernel = kernel_for(model)
        if isinstance(model, model_ns.Mutex):
            init_state = kernel.init_state()
        else:
            init_state = np.array([intern(model.value)], np.int32)
        op_f, op_v = tables(kernel.value_width)
        for i, o in enumerate(ops):
            f_id, v = _op_f_and_values(o, intern)
            op_f[i] = f_id
            op_v[i] = v
        return kernel, init_state, op_f, op_v

    if isinstance(model, model_ns.SetModel):
        ops = list(ops)                  # read in several passes
        if any(o.f not in F_IDS for o in ops) or \
                any(o.f == "add" and o.value is None for o in ops) or \
                any(e is None for e in model.s):
            return _no_kernel(n)
        # Dense element ids: initial elements first, then history order.
        initial_ids = [intern(e) for e in sorted(model.s, key=repr)]
        for o in ops:
            if o.f == "add":
                intern(o.value)
            elif o.f == "read":
                try:
                    for e in (o.value if o.value is not None else ()):
                        intern(e)
                except TypeError:
                    pass
        n_elements = max(1, len(intern.values))
        n_words = -(-n_elements // K.SET_BITS)
        if n_words > MAX_SET_WORDS:
            return _no_kernel(n)
        kernel = K.set_kernel(n_elements, initial_ids)
        op_f, op_v = tables(kernel.value_width)
        for i, o in enumerate(ops):
            op_f[i] = F_IDS[o.f]
            if o.f == "add":
                op_v[i, 0] = intern(o.value)
            elif o.f == "read":
                try:
                    elems = [intern(e) for e in o.value] \
                        if o.value is not None else None
                except TypeError:
                    elems = None
                if elems is not None and int(NIL) in elems:
                    # A None element can never be in the state (nil adds
                    # were rejected above), so this read can never match.
                    elems = None
                if elems is not None:
                    # Observed mask; all-NIL (never matches) when the
                    # read's value is not a collection (= inconsistent).
                    op_v[i, :n_words] = 0
                    for e in elems:
                        op_v[i, e // K.SET_BITS] |= np.int32(
                            1 << (e % K.SET_BITS))
        return kernel, kernel.init_state(), op_f, op_v

    if isinstance(model, (model_ns.UnorderedQueue, model_ns.FIFOQueue)):
        ops = list(ops)                  # read in several passes
        initial = list(model.pending)
        if any(o.f not in F_IDS for o in ops) \
                or any(v is None for v in initial) \
                or any(o.f == "enqueue" and o.value is None for o in ops):
            return _no_kernel(n)
        initial_ids = [intern(v) for v in initial]
        for o in ops:
            if o.f in ("enqueue", "dequeue") and o.value is not None:
                intern(o.value)
        if isinstance(model, model_ns.FIFOQueue):
            depth = _max_queue_depth(ops, len(initial))
            if depth > MAX_FIFO_CAP:
                return _no_kernel(n)
            kernel = K.fifo_queue_kernel(max(1, depth), initial_ids)
        else:
            n_values = max(1, len(intern.values))
            enq_ids = initial_ids + [intern(o.value) for o in ops
                                     if o.f == "enqueue"]
            if len(set(enq_ids)) == len(enq_ids):
                # All enqueued values distinct: pending multiset is a set,
                # packed as a bitmask (31 values/word).
                n_words = -(-n_values // K.SET_BITS)
                if n_words > MAX_SET_WORDS:
                    return _no_kernel(n)
                kernel = K.unordered_unique_kernel(n_values, initial_ids)
            elif n_values <= MAX_QUEUE_VALUES:
                kernel = K.unordered_queue_kernel(n_values, initial_ids)
            else:
                return _no_kernel(n)
        op_f, op_v = tables(kernel.value_width)
        for i, o in enumerate(ops):
            op_f[i] = F_IDS[o.f]
            if o.f in ("enqueue", "dequeue"):
                # A nil dequeue interns to NIL, which is never legal — the
                # same verdict the Python models give (None not in pending,
                # since nil enqueues were rejected above).
                op_v[i, 0] = intern(o.value)
        return kernel, kernel.init_state(), op_f, op_v

    return _no_kernel(n)


def _kernelize_vec(model, cols: OpColumns, intern: _Interner):
    """Vectorized twin of :func:`_kernelize` for the fixed-layout band
    (register / cas-register / mutex) over int-or-None values, read from
    the fast pairing's f and value columns: the first-occurrence
    interner becomes one ``np.unique`` + argsort pass. Returns the
    spec-identical ``(kernel, init_state, op_f, op_v)`` or None when the
    model/value domain defeats the vector form (caller falls back to the
    spec loop, which handles everything)."""
    if not isinstance(model, (model_ns.CASRegister, model_ns.Register,
                              model_ns.Mutex)):
        return None
    n = len(cols.f_code)
    f_tbl = np.array([F_IDS.get(f, -1) for f in cols.f_names], np.int64)
    op_f = f_tbl[cols.f_code] if n else np.zeros(0, np.int64)
    bad = np.flatnonzero(op_f < 0)
    if bad.size:
        raise UnsupportedHistory(
            f"unknown op f={cols.f_names[cols.f_code[bad[0]]]!r} "
            "for device packing")
    kernel = kernel_for(model)
    # The intern-call sequence, in the exact order the spec loop makes
    # them: model.value first (registers), then per op its entries (cas:
    # both words of the pair, read/write: the value, else none).
    is_cas = op_f == F_IDS.get("cas", -9)
    rw = (op_f == F_IDS.get("read", -9)) | (op_f == F_IDS.get("write", -9))
    ent = is_cas * 2 + rw
    starts = np.cumsum(ent) - ent        # seq start of each op's run
    ci = np.flatnonzero(is_cas)
    ri = np.flatnonzero(rw)
    cv = cols.value[ci]
    if not set(map(type, cv)) <= {list, tuple} or \
            not set(map(len, cv)) <= {2}:
        for v in cv.tolist():
            if not isinstance(v, (list, tuple)) or len(v) != 2:
                raise UnsupportedHistory(
                    f"cas value must be a pair: {v!r}")
    off = 0 if isinstance(model, model_ns.Mutex) else 1
    seq = np.empty(off + int(ent.sum()), object)      # None-filled
    if off:
        seq[0] = model.value
    seq[off + starts[ri]] = cols.value[ri]
    seq[off + starts[ci]] = np.fromiter(map(itemgetter(0), cv), object,
                                        len(ci))
    seq[off + starts[ci] + 1] = np.fromiter(map(itemgetter(1), cv),
                                            object, len(ci))
    if not set(map(type, seq)) <= {int, type(None)}:
        return None                      # non-int domain: spec interner
    nn = np.not_equal(seq, None)
    try:
        vals = seq[nn].astype(np.int64)
    except OverflowError:
        return None                      # past int64: spec interner
    ids_all = np.full(len(seq), int(NIL), np.int64)
    if vals.size:
        uniq, first, inverse = np.unique(vals, return_index=True,
                                         return_inverse=True)
        rank = np.argsort(first, kind="stable")   # first-occurrence order
        idmap = np.empty(len(uniq), np.int64)
        idmap[rank] = np.arange(len(uniq))
        ids_all[nn] = idmap[inverse]
        intern.values = uniq[rank].tolist()
        intern.ids = {v: i for i, v in enumerate(intern.values)}
    ids = ids_all[off:].astype(np.int32)
    op_v = np.full((n, kernel.value_width), int(NIL), np.int32)
    op_v[ri, 0] = ids[starts[ri]]
    op_v[ci, 0] = ids[starts[ci]]
    op_v[ci, 1] = ids[starts[ci] + 1]
    if off:
        init_state = np.array([int(ids_all[0])], np.int32)
    else:
        init_state = kernel.init_state()
    return kernel, init_state, op_f.astype(np.int32), op_v


def _pack_events_vec(invoke_pos, return_pos, op_f, op_v, max_window,
                     fill_fv, R):
    """Vectorized twin of the packing walk (JEPSEN_TPU_FAST_PACK): the
    sequential LIFO free-list becomes sort/cumsum passes. An invoke pops
    the most recently freed slot — i.e. returns are opens, invokes are
    closes, and bracket-matching pairs each non-fresh invoke with the
    return whose slot it reuses (within one stack level, opens and
    closes strictly alternate). Fresh invokes (those popping the virgin
    region — exactly the running-min depth records) take slots 0,1,2...
    in order; slots propagate along reuse chains by pointer doubling,
    and the R x W snapshot tables are painted as per-op row intervals
    (cumsum of endpoint deltas). Bit-identical to _pack_events_py
    (fuzzed in tests/test_fast_pack.py); returns arrays already at the
    live window width."""
    n = len(invoke_pos)
    vw = op_v.shape[1]
    nil = int(NIL)
    has_ret = return_pos >= 0
    ret_ids = np.flatnonzero(has_ret)
    ev_pos = np.concatenate([np.asarray(invoke_pos, np.int64),
                             np.asarray(return_pos, np.int64)[ret_ids]])
    ev_op = np.concatenate([np.arange(n, dtype=np.int64), ret_ids])
    n_inv = n
    order = np.argsort(ev_pos, kind="stable")   # endpoint positions unique
    kind_ret = order >= n_inv
    op_s = ev_op[order]
    delta = np.where(kind_ret, -1, 1)
    depth = np.cumsum(delta)
    W_used = int(depth.max(initial=0))
    if W_used > max_window:
        t = int(np.flatnonzero(depth > max_window)[0])
        raise UnsupportedHistory(
            f"concurrency window exceeds {max_window} pending ops "
            f"at history position {int(ev_pos[order[t]])}", kind="window")
    W = max(1, W_used)
    slot = np.zeros(n, np.int32)
    if n:
        # Fresh invokes: the recycle stack is empty exactly when the
        # return-minus-invoke running sum hits a new minimum.
        sigma = np.cumsum(-delta)
        runmin = np.minimum.accumulate(np.minimum(sigma, 0))
        prev_runmin = np.empty_like(runmin)
        prev_runmin[0] = 0
        prev_runmin[1:] = runmin[:-1]
        fresh = (~kind_ret) & (sigma < prev_runmin)
        fresh_ops = op_s[fresh]
        slot_root = np.full(n, -1, np.int32)
        slot_root[fresh_ops] = np.arange(len(fresh_ops), dtype=np.int32)
        # Bracket-match recycled invokes to the return they reuse.
        sub = kind_ret | ((~kind_ret) & ~fresh)
        si = np.flatnonzero(sub)
        lev = sigma - runmin             # stack size after event
        lv = np.where(kind_ret[si], lev[si], lev[si] + 1)
        so = np.argsort(lv, kind="stable")
        ss = si[so]
        lvs = lv[so]
        run_first = np.empty(len(ss), bool)
        if len(ss):
            run_first[0] = True
            run_first[1:] = lvs[1:] != lvs[:-1]
        base = np.maximum.accumulate(
            np.where(run_first, np.arange(len(ss)), 0))
        rank = np.arange(len(ss)) - base
        mpair = rank % 2 == 1            # close at odd rank matches prev
        parent = np.arange(n, dtype=np.int64)
        parent[op_s[ss[mpair]]] = op_s[ss[np.flatnonzero(mpair) - 1]]
        while True:
            pp = parent[parent]
            if np.array_equal(pp, parent):
                break
            parent = pp
        slot = slot_root[parent]
    ret_op = op_s[kind_ret].astype(np.int32)
    ret_slot = slot[ret_op]
    # Row intervals: op i is active in rows [r0, r1) at column slot[i].
    ret_pos_sorted = ev_pos[order[kind_ret]]
    r0 = np.searchsorted(ret_pos_sorted, np.asarray(invoke_pos, np.int64))
    r1 = np.full(n, R, np.int64)
    r1[ret_op] = np.arange(R) + 1
    # Column-major paint (cumsum along the contiguous axis) of op id + 1.
    occ = np.zeros((W, R + 1), np.int32)
    flat = occ.reshape(-1)
    col = slot.astype(np.int64)
    ids1 = np.arange(1, n + 1, dtype=np.int32)
    np.add.at(flat, col * (R + 1) + r0, ids1)
    np.subtract.at(flat, col * (R + 1) + r1, ids1)
    np.cumsum(occ, axis=1, out=occ)
    grid = np.ascontiguousarray(occ[:, :R].T)    # (R, W) op id + 1
    active = grid != 0
    slot_op = grid - 1
    if fill_fv:
        # slot_op is -1 at inactive cells: a sentinel row appended to the
        # per-op tables makes the plain fancy-index land on the inactive
        # fill values there, skipping two full-grid np.where passes.
        op_f_ext = np.concatenate([op_f.astype(np.int32, copy=False),
                                   np.zeros(1, np.int32)])
        op_v_ext = np.concatenate([op_v.astype(np.int32, copy=False),
                                   np.full((1, vw), nil, np.int32)])
        slot_f = op_f_ext[slot_op]
        slot_v = op_v_ext[slot_op]
    else:
        slot_f = np.zeros((R, W), np.int32)
        slot_v = np.full((R, W, vw), nil, np.int32)
    return ret_slot, ret_op, active, slot_f, slot_v, slot_op, W_used


def _pack_events_native(invoke_pos, return_pos, op_f, op_v, max_window,
                        fill_fv, R):
    """The packing walk via native/history_pack.cc (ctypes). None when the
    native library is unavailable or disabled (JEPSEN_TPU_NATIVE_PACK=0
    — fault isolation for the ctypes layer, and the pack bench rung's
    pure-Python spec leg)."""
    if os.environ.get("JEPSEN_TPU_NATIVE_PACK", "") == "0":
        return None
    from jepsen_tpu import native_ext

    try:
        out = native_ext.pack_events(
            invoke_pos, return_pos, op_f, op_v[:, 0], op_v[:, 1],
            nil_value=int(NIL), max_window=max_window,
            fill_fv=fill_fv, R=R)
    except native_ext.WindowOverflow as e:
        raise UnsupportedHistory(
            f"concurrency window exceeds {max_window} pending ops "
            f"at history position {e.pos}", kind="window") from None
    return out


def _pack_events_py(invoke_pos, return_pos, op_f, op_v, max_window,
                    fill_fv, R):
    """Pure-Python packing walk (semantics twin of jtpu_pack_events)."""
    n = len(invoke_pos)
    W_alloc = max_window
    vw = op_v.shape[1]
    ret_slot = np.zeros(R, np.int32)
    ret_op = np.zeros(R, np.int32)
    active = np.zeros((R, W_alloc), bool)
    slot_f = np.zeros((R, W_alloc), np.int32)
    slot_v = np.full((R, W_alloc, vw), int(NIL), np.int32)
    slot_op = np.full((R, W_alloc), -1, np.int32)

    # Event stream over op endpoints: (pos, kind, op_id); invokes before
    # returns at equal positions can't happen (distinct history positions).
    events: list[tuple[int, int, int]] = []
    for i in range(n):
        events.append((int(invoke_pos[i]), 0, i))
        if return_pos[i] >= 0:
            events.append((int(return_pos[i]), 1, i))
    events.sort()

    free = list(range(W_alloc))[::-1]
    slot_of: dict[int, int] = {}
    cur_active: dict[int, int] = {}   # slot -> op id
    max_used = 0
    r = 0
    for pos, kind, i in events:
        if kind == 0:  # invoke
            if not free:
                raise UnsupportedHistory(
                    f"concurrency window exceeds {max_window} pending ops "
                    f"at history position {pos}", kind="window")
            s = free.pop()
            slot_of[i] = s
            cur_active[s] = i
            max_used = max(max_used, s + 1)
        else:  # ok return
            s = slot_of[i]
            ret_slot[r] = s
            ret_op[r] = i
            for slot, op_id in cur_active.items():
                active[r, slot] = True
                slot_op[r, slot] = op_id
                if fill_fv:
                    slot_f[r, slot] = op_f[op_id]
                    slot_v[r, slot] = op_v[op_id]
            r += 1
            del cur_active[s]
            del slot_of[i]
            free.append(s)
    return ret_slot, ret_op, active, slot_f, slot_v, slot_op, max_used


def _pair(history: list[Op], fast: bool):
    """``(ops, invoke_pos, return_pos, ok)`` of :func:`prepare` and
    ``pack_dev.prepack``: the fast pairing's lazy sequence, or the spec
    walk's list with its columns. Counts the LinOps built."""
    if fast:
        obs_trace.count("prepare.ops_built", 0)
        return _pair_ops_vec_arrays(history)
    ops = pair_ops(history)
    obs_trace.count("prepare.ops_built", len(ops))
    n = len(ops)
    return (ops, np.fromiter((o.invoke_pos for o in ops), np.int32, n),
            np.fromiter((-1 if o.return_pos is None else o.return_pos
                         for o in ops), np.int32, n),
            np.fromiter((o.ok for o in ops), bool, n))


def _kernelize_ops(model, ops, intern: _Interner):
    """:func:`_kernelize_vec` over a :class:`LinOpSeq`'s columns where
    it applies, else the spec :func:`_kernelize`."""
    kv = _kernelize_vec(model, ops.cols, intern) \
        if isinstance(ops, LinOpSeq) else None
    return kv if kv is not None else _kernelize(model, ops, intern)


def prepare(model, history, max_window: int = MAX_WINDOW) -> PackedHistory:
    """Pack a history for the frontier search. See module docstring.

    The vectorized fast path (JEPSEN_TPU_FAST_PACK, default on) runs the
    pairing, interning, and slot walk as numpy passes producing output
    BIT-IDENTICAL to the Python spec walk (fuzzed in
    tests/test_fast_pack.py); ``=0`` — or data the vector form does not
    cover — takes the spec path below unchanged."""
    t_start = time.perf_counter()
    fast = fast_pack_enabled()
    # Opened before list(history): it covers all of the host pack.
    with obs_trace.span("prepare", mode="vec" if fast else "spec") as sp:
        with obs_trace.span("prepare.pair"):
            history = list(history)
            ops, invoke_pos, return_pos, ok = _pair(history, fast)
        intern = _Interner()

        # Per-op (f, values) interned ONCE up front — the packing walk
        # below references ops (R x W) times, never re-interning.
        with obs_trace.span("prepare.kernelize"):
            kernel, init_state, op_f, op_v = _kernelize_ops(
                model, ops, intern)
        n = len(ops)
        R = int(ok.sum())

        fill_fv = kernel is not None
        packed = None
        mode = "vec"
        with obs_trace.span("prepare.walk"):
            if fast:
                packed = _pack_events_vec(
                    invoke_pos, return_pos, op_f, op_v, max_window,
                    fill_fv, R)
            if packed is None and op_v.shape[1] == 2:
                # the native walk is specialized to 2-word values
                mode = "native"
                packed = _pack_events_native(
                    invoke_pos, return_pos, op_f, op_v, max_window,
                    fill_fv, R)
            if packed is None:
                mode = "py"
                packed = _pack_events_py(
                    invoke_pos, return_pos, op_f, op_v, max_window,
                    fill_fv, R)
        ret_slot, ret_op, active, slot_f, slot_v, slot_op, max_used = \
            packed

        with obs_trace.span("prepare.tables"):
            crashed = np.flatnonzero(~ok).tolist()

            # Per-slot crashed mask. CONSUMED BY THE DEVICE ENGINES: the
            # crashed-op canonical chains (reduction_tables) and the
            # sparse engine's crashed-subset dominance prune
            # (bfs.expansion_tables builds its key-space crash masks
            # from this; bfs.check_packed gates the prune on it) — its
            # semantics ("this active slot's op never returns") are
            # exactness-critical, not just reporting. Sentinel append:
            # slot_op = -1 (inactive) wraps to a live value, and the &
            # active keeps those cells False, matching the old masked
            # scatter exactly.
            ret_ext = np.concatenate(
                [return_pos.astype(np.int32, copy=False),
                 np.zeros(1, np.int32)])
            crashed_tbl = (ret_ext[slot_op] < 0) & active

            W = max(1, max_used)
            out = PackedHistory(
                model=model, kernel=kernel, ops=ops, window=W, R=R,
                ret_slot=ret_slot, ret_op=ret_op,
                active=active[:, :W], slot_f=slot_f[:, :W],
                slot_v=slot_v[:, :W], slot_op=slot_op[:, :W],
                crashed=crashed_tbl[:, :W],
                init_state=init_state, intern=intern.ids,
                unintern=intern.values, crashed_idx=crashed)
            # Per-op interned tables ride along for the vectorized
            # chain core (reduction_tables); views rebuilt elsewhere
            # (service codec, stream packer) recover them from the slot
            # tables.
            out._op_fv = (op_f, op_v, invoke_pos)
        sp.note(events=len(history), n_ops=n, R=R, W=W, walk=mode)
    _pack_stats["prepare_s"] += time.perf_counter() - t_start
    _pack_stats["prepare_calls"] += 1
    _pack_stats["mode"] = mode
    return out


# --- search-space reductions -------------------------------------------------
#
# Two exact (verdict- and death-row-preserving) reductions of the frontier
# search, consumed by the CPU oracle and the sparse device engine. Both are
# new to this build — knossos has no analogue; they are what lets the sparse
# band (windows 21..64, e.g. cockroach's concurrency-30 registers,
# cockroach.clj:40-41) stay tractable where the JVM search DNFs.
#
# 1. **Pure-op saturation.** A pure op (one whose step never changes state:
#    register/set reads) need not branch the search. Its linearization
#    point can be ANY moment its legality predicate holds between invoke
#    and return, so the search just marks its bit the first moment the
#    config's state matches ("greedy read linearization"). Soundness: read
#    bits are only ever tested positively at the op's return and never
#    affect other transitions, so greedily setting them dominates; any
#    plain survivor maps to a greedy survivor of the same row and vice
#    versa. This removes pure ops from the exponential branching entirely.
#
# 2. **Canonical chains.** Two concurrently-pending identical ops
#    (same f, same value — e.g. two pending write(3)s, two mutex acquires)
#    are exchangeable: swapping their linearization points yields another
#    valid linearization. LIVE ops chain by return order (both intervals
#    cover both points while both are pending, and the earlier-returning
#    interval is the binding one); CRASHED ops chain among themselves by
#    invoke order (their windows never close, so any point past the later
#    invoke lies in every earlier sibling's window). The two families
#    never cross — a crashed op cannot stand in for a live one whose
#    window ends at its return. Slot j with an unlinearized canonical
#    predecessor is blocked until the predecessor's bit is set.
#
# (A third reduction — dominance pruning over crashed-op subsets and
# read bits — lives in the device engine's dedup, jepsen_tpu.lin.bfs
# ._dedup_keys_dom, since it prunes between configs rather than gating
# transitions.)
#
# Config counts on a 2k-op concurrency-30 register history (window 28):
# plain search >170k configs by row 40 (DNF); with both reductions the
# peak frontier is ~20k and the whole history closes.


def _chain_tables_vec(active, slot_f, slot_v, slot_op, op_ordkey,
                      op_crashed, op_f_ops=None, op_v_ops=None):
    """The canonical-chain core of :func:`reduction_tables`, vectorized
    (JEPSEN_TPU_FAST_PACK): the per-row 6-key lexsort becomes one
    rank-compressed int32 key per slot — class rank (lexicographic over
    (f<<1|crashed, value words), via one O(n log n) sort over OPS) in
    the high bits, per-op ordkey rank in the low bits — and a single
    stable per-row argsort. Strictly order-isomorphic to the spec's
    lexsort tuple, so the stable sorts produce identical permutations
    and the identical ``pred``. Shared by the one-shot path and the
    IncrementalPacker (which passes position-based ordkeys).

    ``op_ordkey`` i64[n]: return row / position, crashed past every live
    (unique per op). ``op_crashed`` bool[n]."""
    n_rows, W = active.shape
    pure_fs = {int(K.F_IDS[f]) for f in ("read",) if f in K.F_IDS}
    if len(pure_fs) == 1:
        pure = active & (slot_f == np.int32(next(iter(pure_fs))))
    else:
        pure = active & np.isin(slot_f, list(pure_fs))
    if n_rows == 0:
        return pure, np.full((n_rows, W), -1, np.int32)

    n = len(op_ordkey)
    # Per-op class rank, lexicographic over (f<<1|crashed, v words).
    if op_f_ops is None:
        # Recover per-op f/v from the slot tables (constant per op;
        # every op the chains reference is active in some row).
        op_f_ops = np.zeros(n, np.int64)
        op_v_ops = np.full((n, slot_v.shape[2]), int(NIL), np.int64)
        lin = active.ravel()
        ops_flat = slot_op.ravel()[lin]
        op_f_ops[ops_flat] = slot_f.ravel()[lin]
        op_v_ops[ops_flat] = slot_v.reshape(-1, slot_v.shape[2])[lin]
    else:
        op_f_ops = np.asarray(op_f_ops, np.int64)
        op_v_ops = np.asarray(op_v_ops, np.int64)
    cls_cols = [op_v_ops[:, k] for k in
                range(op_v_ops.shape[1] - 1, -1, -1)]
    cls_cols.append((op_f_ops << 1) | op_crashed)
    o_ops = np.lexsort(tuple(cls_cols))
    chg = np.zeros(n, bool)
    if n > 1:
        for c in cls_cols:
            cs = c[o_ops]
            chg[1:] |= cs[1:] != cs[:-1]
    # Ranks fit int32 for any n < 2^31; int32 fancy-indexing of the
    # (R, W) grids is ~6x faster than int64 on this box.
    cid_sorted = np.cumsum(chg, dtype=np.int32)
    class_rank = np.empty(n, np.int32)
    class_rank[o_ops] = cid_sorted
    n_classes = int(cid_sorted[-1]) + 1 if n else 0
    # Per-op ordkey rank (ordkeys are unique per op).
    ord_rank = np.empty(n, np.int32)
    ord_rank[np.argsort(op_ordkey, kind="stable")] = np.arange(
        n, dtype=np.int32)

    ob = max(1, n).bit_length()
    cb = max(1, W + n_classes).bit_length()
    dtype = np.int32 if (ob + cb) <= 31 else np.int64
    chainable = active & ~pure & (slot_op >= 0)
    # slot_op = -1 wraps to the last op's rank: harmless garbage, masked
    # by ``chainable`` at every use below.
    cls_slot = (class_rank[slot_op] + np.int32(W)).astype(
        dtype, copy=False)
    ord_slot = ord_rank[slot_op].astype(dtype, copy=False)
    sent_cls = (W - 1 - np.arange(W, dtype=dtype))[None, :]
    key = np.where(chainable,
                   (cls_slot << np.array(ob, dtype)) | ord_slot,
                   sent_cls << np.array(ob, dtype))
    idt = np.int32 if n_rows * W < (1 << 31) else np.int64
    order = np.argsort(key, axis=1, kind="stable").astype(
        idt, copy=False)
    cls_key = np.where(chainable, cls_slot, sent_cls).astype(
        np.int32, copy=False)
    # Flat int32 gathers/scatters in place of take/put_along_axis (the
    # int64 index paths are several times slower on this box). Row
    # permutations never collide, so the scatter is well-defined.
    flat = order + (np.arange(n_rows, dtype=idt) * idt(W))[:, None]
    cs = cls_key.ravel()[flat]
    same = cs[:, 1:] == cs[:, :-1]
    pred = np.full(n_rows * W, -1, np.int32)
    pred[flat[:, 1:]] = np.where(same, order[:, :-1], np.int32(-1))
    return pure, pred.reshape(n_rows, W)


def reduction_tables(p: PackedHistory) -> tuple[np.ndarray, np.ndarray]:
    """Per-row reduction tables ``(pure, pred)`` for a packed history.

    pure: bool[R, W] — active slot holds a pure (state-preserving) op.
    pred: i32[R, W]  — canonical-chain predecessor slot (-1 when none):
    slot j may linearize in row r only once ``pred[r, j]``'s bit is set.
    Cached on the PackedHistory after first computation.
    """
    cached = getattr(p, "_reduction_tables", None)
    if cached is not None:
        return cached

    R, W = p.active.shape
    if p.kernel is None or R == 0:
        out = (np.zeros((R, W), bool), np.full((R, W), -1, np.int32))
        p._reduction_tables = out
        return out

    if fast_pack_enabled():
        t0 = time.perf_counter()
        n = len(p.ops)
        ret_row = np.full(n, -1, np.int64)
        ret_row[np.asarray(p.ret_op)] = np.arange(R)
        fv = getattr(p, "_op_fv", (None, None))
        if len(fv) > 2:
            inv_pos = fv[2].astype(np.int64, copy=False)
        else:
            inv_pos = np.fromiter((o.invoke_pos for o in p.ops),
                                  np.int64, n)
        crashed_op = ret_row < 0
        ordkey = np.where(crashed_op, np.int64(R + 2) + inv_pos, ret_row)
        out = _chain_tables_vec(p.active, p.slot_f, p.slot_v, p.slot_op,
                                ordkey, crashed_op, fv[0], fv[1])
        p._reduction_tables = out
        _pack_stats["reduction_s"] += time.perf_counter() - t0
        _pack_stats["reduction_calls"] += 1
        return out
    t0 = time.perf_counter()

    pure_fs = {int(K.F_IDS[f]) for f in ("read",)
               if f in K.F_IDS}
    pure = p.active & np.isin(p.slot_f, list(pure_fs))

    # Return row per slot occurrence: the row at which this slot's op
    # returns; crashed ops get a sentinel past any row.
    NEVER = np.int32(R + 1)
    ret_row_of_op = np.full(len(p.ops), NEVER, np.int64)
    ret_row_of_op[np.asarray(p.ret_op)] = np.arange(R)
    slot_ret = np.where(p.slot_op >= 0,
                        ret_row_of_op[np.clip(p.slot_op, 0, None)], NEVER)

    # Chainable = active, not pure. Identical LIVE ops chain in return
    # order (the earlier-returning interval is the binding one). Identical
    # CRASHED ops (:info, never return — their windows extend to the end
    # of history) chain in INVOKE order: any linearization using a later
    # chain member maps to one using the invoke-order prefix at the same
    # points (each point lies past the later member's invoke, hence past
    # every earlier member's), so WLOG the prefix linearizes first. The
    # two families never cross (a crashed op cannot stand in for a live
    # one whose window ends at its return): the class key carries a
    # crashed flag. This collapses the 2^k subset blowup of k identical
    # crashed mutators — the partitioned-nemesis history shape
    # (BASELINE config 5) — to the k+1 prefixes.
    invoke_of_op = np.fromiter((o.invoke_pos for o in p.ops), np.int64,
                               len(p.ops))
    slot_inv = np.where(p.slot_op >= 0,
                        invoke_of_op[np.clip(p.slot_op, 0, None)], 0)
    is_crashed = slot_ret >= NEVER
    ordkey = np.where(is_crashed, np.int64(R + 2) + slot_inv, slot_ret)

    chainable = p.active & ~pure & (p.slot_op >= 0)
    sent = -1 - np.arange(W, dtype=np.int64)          # unique per column
    f_key = np.where(
        chainable,
        (p.slot_f.astype(np.int64) << 1) | is_crashed,
        sent[None, :])
    v_keys = [p.slot_v[:, :, k].astype(np.int64)
              for k in range(p.slot_v.shape[2])]

    # Row-wise canonical order: sort slots by (class, return row | invoke
    # position); equal classes become adjacent runs in canonical order.
    order = np.lexsort(tuple([ordkey] + v_keys[::-1] + [f_key]), axis=1)
    rows = np.arange(R)[:, None]
    f_s = np.take_along_axis(f_key, order, axis=1)
    same = f_s[:, 1:] == f_s[:, :-1]
    for vk in v_keys:
        v_s = np.take_along_axis(vk, order, axis=1)
        same &= v_s[:, 1:] == v_s[:, :-1]
    pred = np.full((R, W), -1, np.int32)
    cols = order[:, 1:]
    prev = order[:, :-1]
    np.put_along_axis(
        pred, cols, np.where(same, prev, -1).astype(np.int32), axis=1)
    out = (pure, pred)
    p._reduction_tables = out
    _pack_stats["reduction_s"] += time.perf_counter() - t0
    _pack_stats["reduction_calls"] += 1
    return out


# --- pure-python packed step (mirror of models.kernels, for the CPU
# reference checker's inner loop and witness replay) -------------------------

def py_step_fn(kernel_name: str) -> Callable:
    """Python twin of the device step kernels, operating on
    (state tuple, f id, value ids) — must agree exactly with
    jepsen_tpu.models.kernels (parity-tested)."""
    from jepsen_tpu.models import kernels as K

    nil = int(K.NIL)

    if kernel_name in ("cas-register", "register"):
        allow_cas = kernel_name == "cas-register"

        def step(state, f, v):
            cur = state[0]
            if f == K.F_READ:
                return (v[0] == nil or v[0] == cur), state
            if f == K.F_WRITE:
                return True, (v[0],)
            if f == K.F_CAS and allow_cas:
                if v[0] == cur:
                    return True, (v[1],)
                return False, state
            return False, state

        return step

    if kernel_name == "mutex":
        def step(state, f, v):
            locked = state[0]
            if f == K.F_ACQUIRE:
                return locked == 0, (1,)
            if f == K.F_RELEASE:
                return locked == 1, (0,)
            return False, state

        return step

    if kernel_name == "set":
        def step(state, f, v):
            if f == K.F_ADD:
                e = v[0]
                if e == nil:
                    return False, state
                w, b = divmod(e, K.SET_BITS)
                s = list(state)
                s[w] |= 1 << b
                return True, tuple(s)
            if f == K.F_READ:
                return tuple(v[:len(state)]) == tuple(state), state
            return False, state

        return step

    if kernel_name == "unordered-unique":
        def step(state, f, v):
            e = v[0]
            if e == nil:
                return False, state
            w, b = divmod(e, K.SET_BITS)
            has = bool((state[w] >> b) & 1)
            if f == K.F_ENQUEUE and not has:
                s = list(state)
                s[w] |= 1 << b
                return True, tuple(s)
            if f == K.F_DEQUEUE and has:
                s = list(state)
                s[w] &= ~(1 << b)
                return True, tuple(s)
            return False, state

        return step

    if kernel_name == "unordered-queue":
        def step(state, f, v):
            e = v[0]
            if f == K.F_ENQUEUE:
                s = list(state)
                s[e] += 1
                return True, tuple(s)
            if f == K.F_DEQUEUE:
                if 0 <= e < len(state) and state[e] > 0:
                    s = list(state)
                    s[e] -= 1
                    return True, tuple(s)
                return False, state
            return False, state

        return step

    if kernel_name == "fifo-queue":
        def step(state, f, v):
            size, buf = state[0], state[1:]
            if f == K.F_ENQUEUE:
                if size >= len(buf):
                    return False, state
                s = list(buf)
                s[size] = v[0]
                return True, (size + 1, *s)
            if f == K.F_DEQUEUE:
                if size > 0 and buf[0] == v[0]:
                    return True, (size - 1, *buf[1:], 0)
                return False, state
            return False, state

        return step

    raise ValueError(f"no python step for kernel {kernel_name!r}")
