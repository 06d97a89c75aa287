"""Device-resident history packer: the slot walk on the accelerator.

The host packer (``prepare``) runs pairing + interning (Python-object
work by nature) and then three numeric column passes — the endpoint
slot walk, the R x W snapshot paint, and the canonical-chain tables.
Under FAST_PACK those passes are already pure integer sort / cumsum /
searchsorted / scatter algebra (prepare._pack_events_vec,
prepare._chain_tables_vec), i.e. exactly the shapes XLA runs well.
This module splits the pack at that boundary:

- :func:`prepack` does the host-only half — pairing, kernelizing,
  interning, and the O(E log E) window/overflow scan — producing a
  :class:`PrePacked` column bundle that already answers everything the
  service admission tier needs (shape bin, fingerprint, window/R)
  WITHOUT painting the R x W grids. It raises the exact
  ``UnsupportedHistory`` errors ``prepare.prepare`` would.
- :func:`materialize` / :func:`materialize_batch` finish the pack on
  the DEVICE: one jitted program runs the event sort, the
  running-minimum fresh-slot detection, the level-sorted bracket
  pairing, pointer-doubling slot propagation, the interval paint, the
  snapshot gathers, the crashed table, and the chain tables — the
  whole O(R x W) tail — and the batched entry vmaps K same-shape
  histories through it as ONE dispatch. Output is BIT-IDENTICAL to
  the spec walk (fuzzed in tests/test_pack_dev.py, gated in
  ``make pack-smoke``).

Padding (static shapes, one compile per shape bucket): ops pad to a
power-of-two ``n_pad`` with inert synthetic ops at positions past
every real event — the first ``R_pad - R`` pads invoke and return as
sequential non-overlapping pairs (filling the return-event axis; their
rows land in ``[R, R_pad)`` and are sliced off), the rest invoke and
never return (crashed pads; their paint interval ``[r0, r1)`` is
empty). Pad events sort AFTER every real event, so the real prefix of
every scan (depth, running min, bracket levels) is untouched; a pad
that bracket-matches a real return merely reuses its slot for rows
that are sliced off. Pads that go past the real window paint into a
dump column that is also sliced off.

Every device dispatch rides the supervision stack as site ``pack-dev``
(watchdog -> quarantine -> honest fallback to the proven FAST_PACK
numpy path — a pack fallback can never cost a verdict), is
static-gate analyzed (the traceable is the pure program), span-traced
(``pack-dev`` spans), and feeds the pack meter. Knobs:
``JEPSEN_TPU_PACK_DEV`` (default on), ``JEPSEN_TPU_PACK_DEV_MIN_K``,
``JEPSEN_TPU_PACK_DEV_STREAM_ROWS`` — tabled in doc/env.md.
"""

from __future__ import annotations

import hashlib
import os
import time
from collections.abc import Sequence
from dataclasses import dataclass
from typing import Any

import numpy as np

from jepsen_tpu.lin import prepare
from jepsen_tpu.lin.prepare import PackedHistory, UnsupportedHistory
from jepsen_tpu.models.kernels import F_IDS, NIL


def pack_dev_enabled() -> bool:
    """``JEPSEN_TPU_PACK_DEV``: the device packer (default on; ``=0``
    keeps every materialization on the host FAST_PACK path). Re-read
    per call (the env-knob convention, doc/env.md)."""
    return os.environ.get("JEPSEN_TPU_PACK_DEV", "") != "0"


def min_batch_k() -> int:
    """``JEPSEN_TPU_PACK_DEV_MIN_K``: bin-wave occupancy below which
    the daemon materializes on the host instead of dispatching the
    batched device pack (per-dispatch overhead dominates small
    waves; the bench pack rung's device leg is the evidence)."""
    from jepsen_tpu import util

    return util.env_int("JEPSEN_TPU_PACK_DEV_MIN_K", 4)


def stream_min_rows() -> int:
    """``JEPSEN_TPU_PACK_DEV_STREAM_ROWS``: settled-row count below
    which a stream increment paints on the host (the device paint is
    one more dispatch between frontier dispatches — only worth it for
    big settle batches)."""
    from jepsen_tpu import util

    return util.env_int("JEPSEN_TPU_PACK_DEV_STREAM_ROWS", 512)


# Device-pack accounting (pack-smoke, the service stats block, and the
# bench pack rung's device leg read this; reset per process).
_dev_stats = {"dev_packs": 0, "dev_lanes": 0, "dev_pack_s": 0.0,
              "host_fallbacks": 0, "quarantine_skips": 0,
              "wedges": 0, "faults": 0, "static_skips": 0}


def dev_stats() -> dict:
    return dict(_dev_stats)


def reset_dev_stats() -> None:
    for k in _dev_stats:
        _dev_stats[k] = 0.0 if k.endswith("_s") else 0


@dataclass
class PrePacked:
    """The host half of a pack: pairing + interning done, numeric
    columns ready, grids NOT painted. Exposes the attributes
    ``service.daemon.bin_key`` / ``dense.plan`` read (kernel, window,
    R, state_width, unintern, init_state), so admission can bin and
    fingerprint a request without the R x W paint."""

    model: Any
    kernel: Any                  # KernelModel | None
    ops: Sequence                # LinOps (reporting / witnesses)
    window: int                  # W_used (exact, from the depth scan)
    R: int
    n: int
    invoke_pos: np.ndarray       # i32[n]
    return_pos: np.ndarray       # i32[n]  (-1 = crashed)
    op_f: np.ndarray             # i32[n]
    op_v: np.ndarray             # i32[n, vw]
    init_state: np.ndarray
    intern: dict
    unintern: list
    crashed_idx: list            # indices into ops of the crashed ops

    @property
    def state_width(self) -> int:
        return len(self.init_state)


def prepack(model, history,
            max_window: int = prepare.MAX_WINDOW) -> PrePacked:
    """Pairing + kernelize + the O(E log E) window scan — everything
    ``prepare.prepare`` does BEFORE the grid paint, raising the same
    ``UnsupportedHistory`` errors (double-invoke, unknown f, cas pair,
    window overflow) at admission time."""
    from jepsen_tpu.obs import trace as obs_trace

    t0 = time.perf_counter()
    history = list(history)
    fast = prepare.fast_pack_enabled()
    with obs_trace.span("prepack", events=len(history)) as sp:
        ops, invoke_pos, return_pos, ok = prepare._pair(history, fast)
        intern = prepare._Interner()
        kernel, init_state, op_f, op_v = prepare._kernelize_ops(
            model, ops, intern)
        n = len(ops)
        R = int(ok.sum())
        W_used = _window_scan(invoke_pos, return_pos, max_window)
        crashed = np.flatnonzero(~ok).tolist()
        sp.note(n_ops=n, R=R, W=W_used)
    st = prepare._pack_stats
    st["prepare_s"] += time.perf_counter() - t0
    return PrePacked(
        model=model, kernel=kernel, ops=ops, window=max(1, W_used),
        R=R, n=n,
        invoke_pos=np.asarray(invoke_pos, np.int32),
        return_pos=np.asarray(return_pos, np.int32),
        op_f=np.asarray(op_f, np.int32),
        op_v=np.asarray(op_v, np.int32),
        init_state=init_state, intern=intern.ids,
        unintern=intern.values, crashed_idx=crashed)


def _window_scan(invoke_pos, return_pos, max_window: int) -> int:
    """Exact W_used + the overflow check — prepare._pack_events_vec's
    depth scan, standalone (the device program never sees an
    overflowing history)."""
    n = len(invoke_pos)
    if n == 0:
        return 0
    ret_ids = np.flatnonzero(np.asarray(return_pos) >= 0)
    ev_pos = np.concatenate([np.asarray(invoke_pos, np.int64),
                             np.asarray(return_pos, np.int64)[ret_ids]])
    order = np.argsort(ev_pos, kind="stable")
    delta = np.where(order >= n, -1, 1)
    depth = np.cumsum(delta)
    W_used = int(depth.max(initial=0))
    if W_used > max_window:
        t = int(np.flatnonzero(depth > max_window)[0])
        raise UnsupportedHistory(
            f"concurrency window exceeds {max_window} pending ops "
            f"at history position {int(ev_pos[order[t]])}",
            kind="window")
    return W_used


def prepack_fingerprint(pre: PrePacked) -> str:
    """History identity over the PRE-pack columns: the admission tier
    needs the fingerprint before the grids exist, and the grids are a
    pure function of these columns — so hashing the columns identifies
    at least as finely as ``supervise.history_fingerprint`` over the
    painted tables. This is the service-wire fingerprint (journal
    admits, ``result-fetch``, the chaos oracle audits):
    ``protocol.request_fingerprint`` computes the SAME function
    client-side, bit for bit. The checkpoint/resume identity
    (``supervise.history_fingerprint``) is a separate contract over
    packed tables and is unchanged."""
    h = hashlib.sha256()
    h.update(
        f"{pre.kernel.name if pre.kernel else None}|{pre.window}|"
        f"{pre.R}|{len(pre.unintern)}".encode())
    for a in (pre.invoke_pos, pre.return_pos, pre.op_f, pre.op_v,
              pre.init_state):
        arr = np.ascontiguousarray(np.asarray(a))
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


# --- the device program ------------------------------------------------------


def _pow2(x: int, floor: int = 1) -> int:
    return max(floor, 1 << max(0, (int(x) - 1).bit_length()))


def pad_shape(n: int, R: int, W: int, vw: int) -> tuple:
    """The static shape bucket one history compiles into: pow2 R and
    W axes, and an op axis with room for the ``R_pad - R`` returning
    pads (n_pad - n >= R_pad - R by construction)."""
    r_pad = _pow2(R, 4)
    w_pad = _pow2(W, 4)
    n_pad = _pow2(n + r_pad - R, 8)
    return n_pad, r_pad, w_pad, vw


def _pad_columns(pre: PrePacked, shape: tuple):
    """Host-side pad to the static bucket: synthetic ops at sequential
    positions past every real event (module docstring). Returns the
    per-lane device inputs as numpy arrays."""
    n_pad, r_pad, w_pad, vw = shape
    n, R = pre.n, pre.R
    inv = np.zeros(n_pad, np.int32)
    ret = np.full(n_pad, -1, np.int32)
    inv[:n] = pre.invoke_pos
    ret[:n] = pre.return_pos
    big = np.int32(0)
    if n:
        big = max(int(pre.invoke_pos.max(initial=0)),
                  int(pre.return_pos.max(initial=0))) + 1
    p_ret = r_pad - R                    # returning pads (fill R axis)
    j = np.arange(n_pad - n, dtype=np.int32)
    inv[n:] = big + 2 * j
    ret[n:n + p_ret] = big + 2 * j[:p_ret] + 1
    op_f = np.zeros(n_pad + 1, np.int32)
    op_v = np.full((n_pad + 1, vw), int(NIL), np.int32)
    op_f[:n] = pre.op_f
    op_v[:n] = pre.op_v
    # Return-event column (static R_pad returns): real returns in op
    # order, then the returning pads.
    ret_ids = np.flatnonzero(pre.return_pos >= 0).astype(np.int32)
    ev_rop = np.concatenate([ret_ids,
                             n + j[:p_ret]]).astype(np.int32)
    ev_rpos = ret[ev_rop]
    # Per-op chain ranks (prepare._chain_tables_vec's host half): class
    # rank lexicographic over (f<<1|crashed, value words), ordkey rank
    # over (return row | R+2+invoke position) — both O(n log n) host
    # sorts; the per-row stable sort happens on device.
    cls_rank = np.zeros(n_pad + 1, np.int32)
    ord_rank = np.zeros(n_pad + 1, np.int32)
    if n:
        ret_row = np.full(n, -1, np.int64)
        order_r = np.argsort(pre.return_pos[ret_ids], kind="stable")
        ret_row[ret_ids[order_r]] = np.arange(R)
        crashed_op = ret_row < 0
        ordkey = np.where(crashed_op,
                          np.int64(R + 2)
                          + pre.invoke_pos.astype(np.int64), ret_row)
        cls_cols = [pre.op_v[:, k].astype(np.int64)
                    for k in range(vw - 1, -1, -1)]
        cls_cols.append((pre.op_f.astype(np.int64) << 1) | crashed_op)
        o_ops = np.lexsort(tuple(cls_cols))
        chg = np.zeros(n, bool)
        if n > 1:
            for c in cls_cols:
                cs = c[o_ops]
                chg[1:] |= cs[1:] != cs[:-1]
        cls_rank[:n][o_ops] = np.cumsum(chg, dtype=np.int32)
        ord_rank[:n][np.argsort(ordkey, kind="stable")] = \
            np.arange(n, dtype=np.int32)
    return (inv, ret, ev_rop, ev_rpos, op_f, op_v, cls_rank, ord_rank)


def _pack_program(shape: tuple, f_read: int):
    """The single-lane jitted pack: event sort -> fresh detection ->
    bracket pairing -> pointer-doubling slot propagation -> interval
    paint -> snapshot gathers -> crashed table -> chain tables, all
    static-shape jax. Cached per shape bucket."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_pad, r_pad, w_pad, vw = shape
    e_tot = n_pad + r_pad
    doublings = max(1, (n_pad - 1).bit_length())

    def pack(inv, ret, ev_rop, ev_rpos, op_f, op_v, cls_rank,
             ord_rank):
        # Endpoint events: invokes [0, n_pad) + returns [n_pad, e_tot),
        # sorted by position (positions are unique, so the plain sort
        # is the spec's stable argsort).
        ev_pos = jnp.concatenate([inv, ev_rpos])
        ev_op = jnp.concatenate(
            [jnp.arange(n_pad, dtype=jnp.int32), ev_rop])
        ev_isret = jnp.concatenate(
            [jnp.zeros(n_pad, jnp.int32), jnp.ones(r_pad, jnp.int32)])
        pos_s, op_s, kind_i = lax.sort(
            (ev_pos, ev_op, ev_isret), num_keys=1)
        kind_ret = kind_i == 1
        # Fresh invokes: new running minima of the return-minus-invoke
        # sum take virgin slots 0,1,2... in order.
        delta = jnp.where(kind_ret, -1, 1).astype(jnp.int32)
        sigma = jnp.cumsum(-delta)
        runmin = lax.cummin(jnp.minimum(sigma, 0))
        prev_runmin = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), runmin[:-1]])
        fresh = (~kind_ret) & (sigma < prev_runmin)
        fresh_rank = (jnp.cumsum(fresh.astype(jnp.int32)) - 1)
        slot_root = jnp.full(n_pad + 1, -1, jnp.int32)
        slot_root = slot_root.at[
            jnp.where(fresh, op_s, n_pad)].set(
            jnp.where(fresh, fresh_rank, -1), mode="drop")
        # Bracket-match recycled invokes (closes) to the return whose
        # slot they reuse (opens) — stable level sort, odd ranks match
        # their predecessor within the level run.
        sub = kind_ret | ((~kind_ret) & ~fresh)
        lev = sigma - runmin
        lv = jnp.where(kind_ret, lev, lev + 1)
        big_lv = jnp.int32(e_tot + w_pad + 2)
        lv_key = jnp.where(sub, lv, big_lv)
        idx = jnp.arange(e_tot, dtype=jnp.int32)
        lvs, ss, subs_s = lax.sort(
            (lv_key, idx, sub.astype(jnp.int32)), num_keys=2)
        run_first = jnp.concatenate(
            [jnp.ones(1, bool), lvs[1:] != lvs[:-1]])
        base = lax.cummax(jnp.where(run_first, idx, 0))
        rank = idx - base
        prev_op = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), op_s[ss][:-1]])
        mpair = (rank % 2 == 1) & (subs_s == 1)
        parent = jnp.arange(n_pad + 1, dtype=jnp.int32)
        parent = parent.at[
            jnp.where(mpair, op_s[ss], n_pad)].set(
            jnp.where(mpair, prev_op, n_pad), mode="drop")
        for _ in range(doublings):        # fixed-trip pointer doubling
            parent = parent[parent]
        slot = slot_root[parent[:n_pad]]
        # Return-event tables, in sorted event order.
        ret_rank = jnp.cumsum(kind_ret.astype(jnp.int32)) - 1
        ret_op = jnp.zeros(r_pad, jnp.int32).at[
            jnp.where(kind_ret, ret_rank, r_pad)].set(
            op_s, mode="drop")
        ret_pos_sorted = jnp.zeros(r_pad, jnp.int32).at[
            jnp.where(kind_ret, ret_rank, r_pad)].set(
            pos_s, mode="drop")
        ret_slot = slot[ret_op]
        # Row intervals: op i is active in rows [r0, r1) at column
        # slot[i]; paint op id + 1 by endpoint deltas + cumsum.
        r0 = jnp.searchsorted(ret_pos_sorted, inv)
        r1 = jnp.full(n_pad, r_pad, jnp.int32).at[ret_op].set(
            jnp.arange(1, r_pad + 1, dtype=jnp.int32))
        col = jnp.where((slot < 0) | (slot >= w_pad), w_pad, slot)
        ids1 = jnp.arange(1, n_pad + 1, dtype=jnp.int32)
        occ = jnp.zeros((w_pad + 1) * (r_pad + 1), jnp.int32)
        occ = occ.at[col * (r_pad + 1) + r0].add(ids1, mode="drop")
        occ = occ.at[col * (r_pad + 1) + r1].add(-ids1, mode="drop")
        occ = jnp.cumsum(occ.reshape(w_pad + 1, r_pad + 1), axis=1)
        grid = occ[:w_pad, :r_pad].T
        active = grid != 0
        slot_op = grid - 1                    # -1 at inactive cells
        gidx = jnp.where(active, slot_op, n_pad)
        slot_f = op_f[gidx]
        slot_v = op_v[gidx]
        ret_ext = jnp.concatenate(
            [ret[:n_pad], jnp.zeros(1, jnp.int32)])
        crashed = (ret_ext[gidx] < 0) & active
        # Chain tables (prepare._chain_tables_vec's per-row half): the
        # class/ordkey ranks came from the host sorts; the row-wise
        # canonical sort runs here. Key order is identical to the spec
        # (lexicographic (class, ordkey-rank); sentinels per column
        # below every chainable class), so the pred table's real
        # region matches bit for bit after the slice.
        pure = active & (slot_f == f_read)
        chainable = active & (~pure) & (slot_op >= 0)
        cls_slot = cls_rank[gidx] + jnp.int32(w_pad)
        ord_slot = ord_rank[gidx]
        sent = (w_pad - 1
                - jnp.arange(w_pad, dtype=jnp.int32))[None, :]
        sent = jnp.broadcast_to(sent, (r_pad, w_pad))
        key_hi = jnp.where(chainable, cls_slot, sent)
        key_lo = jnp.where(chainable, ord_slot, 0)
        cols = jnp.broadcast_to(
            jnp.arange(w_pad, dtype=jnp.int32)[None, :],
            (r_pad, w_pad))
        _, _, order = lax.sort((key_hi, key_lo, cols), num_keys=2,
                               dimension=1)
        rows_off = (jnp.arange(r_pad, dtype=jnp.int32)
                    * jnp.int32(w_pad))[:, None]
        cs = key_hi.reshape(-1)[order + rows_off]
        same = cs[:, 1:] == cs[:, :-1]
        pred = jnp.full(r_pad * w_pad, -1, jnp.int32)
        pred = pred.at[(order[:, 1:] + rows_off).reshape(-1)].set(
            jnp.where(same, order[:, :-1], -1).reshape(-1),
            mode="drop")
        pred = pred.reshape(r_pad, w_pad)
        return (ret_slot, ret_op, active, slot_f, slot_v, slot_op,
                crashed, pure, pred)

    return pack


_program_cache: dict = {}


def _compiled(shape: tuple, batched: bool):
    """jit(program) / jit(vmap(program)) per static shape bucket."""
    import jax

    key = (shape, batched)
    fn = _program_cache.get(key)
    if fn is None:
        f_read = int(F_IDS["read"])
        prog = _pack_program(shape, f_read)
        fn = jax.jit(jax.vmap(prog) if batched else prog)
        _program_cache[key] = fn
    return fn


def pack_traceable(shape: tuple, lanes: int = 0):
    """A no-arg pure-jax callable of the pack program at ``shape``
    (vmapped over ``lanes`` when > 0) over zero inputs — what the
    static gate traces and tests/test_analysis.py lints."""
    import jax.numpy as jnp

    n_pad, r_pad, w_pad, vw = shape
    prog = _pack_program(shape, int(F_IDS["read"]))

    def args():
        z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
        one = (z(n_pad), z(n_pad), z(r_pad), z(r_pad), z(n_pad + 1),
               z(n_pad + 1, vw), z(n_pad + 1), z(n_pad + 1))
        if not lanes:
            return one
        return tuple(jnp.broadcast_to(a, (lanes,) + a.shape)
                     for a in one)

    if not lanes:
        return lambda: prog(*args())
    import jax

    vprog = jax.vmap(prog)
    return lambda: vprog(*args())


# --- materialization ---------------------------------------------------------


def _host_materialize(pre: PrePacked) -> PackedHistory:
    """The proven FAST_PACK numpy path over the prepack columns —
    prepare.prepare's grid half, byte-identical (the honest fallback
    rung under the pack-dev site, and the non-device default)."""
    t0 = time.perf_counter()
    fill_fv = pre.kernel is not None
    packed = None
    if prepare.fast_pack_enabled():
        packed = prepare._pack_events_vec(
            pre.invoke_pos, pre.return_pos, pre.op_f, pre.op_v,
            prepare.MAX_WINDOW, fill_fv, pre.R)
    if packed is None and pre.op_v.shape[1] == 2:
        packed = prepare._pack_events_native(
            pre.invoke_pos, pre.return_pos, pre.op_f, pre.op_v,
            prepare.MAX_WINDOW, fill_fv, pre.R)
    if packed is None:
        packed = prepare._pack_events_py(
            pre.invoke_pos, pre.return_pos, pre.op_f, pre.op_v,
            prepare.MAX_WINDOW, fill_fv, pre.R)
    out = _assemble(pre, *packed[:6])
    st = prepare._pack_stats
    st["prepare_s"] += time.perf_counter() - t0
    st["prepare_calls"] += 1
    st["mode"] = "vec" if prepare.fast_pack_enabled() else "py"
    return out


def _assemble(pre: PrePacked, ret_slot, ret_op, active, slot_f,
              slot_v, slot_op) -> PackedHistory:
    """PackedHistory from grid tables at (>=W) width — the same
    construction (crashed sentinel trick included) as
    prepare.prepare."""
    W = pre.window
    ret_ext = np.concatenate(
        [pre.return_pos.astype(np.int32, copy=False),
         np.zeros(1, np.int32)])
    crashed_tbl = (ret_ext[slot_op] < 0) & active
    out = PackedHistory(
        model=pre.model, kernel=pre.kernel, ops=pre.ops, window=W,
        R=pre.R, ret_slot=ret_slot, ret_op=ret_op,
        active=active[:, :W], slot_f=slot_f[:, :W],
        slot_v=slot_v[:, :W], slot_op=slot_op[:, :W],
        crashed=crashed_tbl[:, :W], init_state=pre.init_state,
        intern=pre.intern, unintern=pre.unintern,
        crashed_idx=pre.crashed_idx)
    out._op_fv = (pre.op_f, pre.op_v, pre.invoke_pos)
    return out


def _assemble_dev(pre: PrePacked, lane) -> PackedHistory:
    """PackedHistory from one device lane's fetched outputs (sliced
    to the real R x W region, spec dtypes)."""
    R, W = pre.R, pre.window
    (ret_slot, ret_op, active, slot_f, slot_v, slot_op, crashed,
     pure, pred) = lane
    out = PackedHistory(
        model=pre.model, kernel=pre.kernel, ops=pre.ops, window=W,
        R=R,
        ret_slot=np.ascontiguousarray(ret_slot[:R], np.int32),
        ret_op=np.ascontiguousarray(ret_op[:R], np.int32),
        active=np.ascontiguousarray(active[:R, :W]),
        slot_f=np.ascontiguousarray(slot_f[:R, :W], np.int32),
        slot_v=np.ascontiguousarray(slot_v[:R, :W], np.int32),
        slot_op=np.ascontiguousarray(slot_op[:R, :W], np.int32),
        crashed=np.ascontiguousarray(crashed[:R, :W]),
        init_state=pre.init_state, intern=pre.intern,
        unintern=pre.unintern, crashed_idx=pre.crashed_idx)
    out._op_fv = (pre.op_f, pre.op_v, pre.invoke_pos)
    out._reduction_tables = (
        np.ascontiguousarray(pure[:R, :W]),
        np.ascontiguousarray(pred[:R, :W], np.int32))
    return out


def _device_eligible(pre: PrePacked) -> bool:
    # kernel-less histories never bin (generic CPU search takes them),
    # and R == 0 has no grids worth a dispatch.
    return pre.kernel is not None and pre.R > 0 and pre.n > 0


def _shape_key(shape: tuple, lanes: int) -> str:
    from jepsen_tpu.lin import supervise

    n_pad, r_pad, w_pad, vw = shape
    return supervise.shape_key("pack-dev", cap=n_pad, window=w_pad,
                               kernel=f"pack-vw{vw}",
                               rows=max(1, lanes), band=f"r{r_pad}")


def materialize(pre: PrePacked, *, stats: dict | None = None
                ) -> PackedHistory:
    """Finish one pack: the supervised device program when eligible
    and enabled, else (or on wedge / fault / quarantine / static
    flag) the host FAST_PACK path. Verdict-neutral by construction —
    both rungs produce the bit-identical PackedHistory."""
    if not (pack_dev_enabled() and _device_eligible(pre)):
        return _host_materialize(pre)
    out = _materialize_wave([pre], stats=stats, batched=False)
    return out[0]


def materialize_batch(pres: list, *, stats: dict | None = None
                      ) -> list:
    """Pack K histories; same-bucket eligible lanes ride ONE vmapped
    device dispatch (the daemon's bin-wave admission offload), the
    rest take the host path. Waves below ``min_batch_k()`` —
    singletons included — pack host-side: the device program only
    amortizes its dispatch + compile overhead across K lanes
    (doc/env.md § JEPSEN_TPU_PACK_DEV_MIN_K; :func:`materialize` is
    the explicit single-pack device entry). Order-preserving."""
    out: list = [None] * len(pres)
    groups: dict = {}
    for i, pre in enumerate(pres):
        if pack_dev_enabled() and _device_eligible(pre):
            shape = pad_shape(pre.n, pre.R, pre.window,
                              pre.op_v.shape[1])
            groups.setdefault(shape, []).append(i)
        else:
            out[i] = _host_materialize(pre)
    for shape, ix in groups.items():
        wave = [pres[i] for i in ix]
        if len(wave) < max(1, min_batch_k()):
            packs = [_host_materialize(p) for p in wave]
        else:
            packs = _materialize_wave(
                wave, stats=stats, batched=len(wave) > 1)
        for i, p in zip(ix, packs):
            out[i] = p
    return out


def _materialize_wave(wave: list, *, stats: dict | None,
                      batched: bool) -> list:
    """One supervised pack-dev dispatch over same-bucket lanes, host
    fallback per lane on any non-ok outcome."""
    from jepsen_tpu.lin import supervise
    from jepsen_tpu.obs import trace as obs_trace

    pre0 = wave[0]
    shape = pad_shape(pre0.n, pre0.R, pre0.window,
                      pre0.op_v.shape[1])
    key = _shape_key(shape, len(wave) if batched else 1)
    if supervise.quarantined(key) is not None:
        _dev_stats["quarantine_skips"] += 1
        _dev_stats["host_fallbacks"] += len(wave)
        obs_trace.instant("pack-dev-skip", key=key,
                          reason="quarantined")
        return [_host_materialize(p) for p in wave]
    t0 = time.perf_counter()
    cols = [_pad_columns(p, shape) for p in wave]
    if batched:
        args = tuple(np.stack([c[k] for c in cols])
                     for k in range(8))
    else:
        args = cols[0]
    fn = _compiled(shape, batched)

    def thunk():
        import jax

        res = fn(*args)
        return jax.device_get(res)

    with obs_trace.span("pack-dev", lanes=len(wave),
                        shape=str(shape)) as sp:
        outcome, res = supervise.run_guarded(
            "pack-dev", key, thunk, stats=stats,
            traceable=pack_traceable(
                shape, lanes=len(wave) if batched else 0))
        sp.note(outcome=outcome)
    if outcome != "ok":
        _dev_stats["host_fallbacks"] += len(wave)
        _dev_stats["wedges" if outcome == "wedge" else
                    "faults" if outcome == "fault" else
                    "static_skips"] += 1
        return [_host_materialize(p) for p in wave]
    dt = time.perf_counter() - t0
    _dev_stats["dev_packs"] += 1
    _dev_stats["dev_lanes"] += len(wave)
    _dev_stats["dev_pack_s"] += dt
    st = prepare._pack_stats
    st["prepare_s"] += dt
    st["prepare_calls"] += len(wave)
    st["mode"] = "dev"
    if batched:
        return [_assemble_dev(p, tuple(np.asarray(a[i])
                                       for a in res))
                for i, p in enumerate(wave)]
    return [_assemble_dev(wave[0], tuple(np.asarray(a)
                                         for a in res))]


# --- the streaming paint (stream/incr.py's settled-row increments) ----------


def _paint_program(W: int, rows_pad: int, vw: int):
    """The stream settle's grid half on device: interval paint +
    snapshot gathers over the carried painter set (stream/incr.py
    computes painters/slots/intervals host-side with carried state —
    the O(rows x W) tail runs here). ``op_crash`` is a host-computed
    bool column (the stream's never-returns sentinel is an int64 the
    int32-only device never sees)."""
    import jax.numpy as jnp

    def paint(p_slot, r0, r1, ids1, opf, opv, op_crash, n1):
        col = jnp.where((p_slot < 0) | (p_slot >= W), W, p_slot)
        occ = jnp.zeros((W + 1) * (rows_pad + 1), jnp.int32)
        occ = occ.at[col * (rows_pad + 1) + r0].add(ids1, mode="drop")
        occ = occ.at[col * (rows_pad + 1) + r1].add(-ids1,
                                                   mode="drop")
        occ = jnp.cumsum(occ.reshape(W + 1, rows_pad + 1), axis=1)
        grid = occ[:W, :rows_pad].T
        active = grid != 0
        slot_op = grid - 1
        gidx = jnp.where(active, slot_op, n1)
        slot_f = opf[gidx]
        slot_v = opv[gidx]
        crashed = op_crash[gidx] & active
        return grid, active, slot_f, slot_v, slot_op, crashed

    return paint


_paint_cache: dict = {}


def paint_tables_dev(p_slot, r0, r1, ids1, op_f, op_v, op_crashed,
                     n1: int, n_new: int, W: int, *,
                     kernel: str, stats: dict | None = None):
    """Supervised device paint for one stream settle batch. Returns
    the (grid, active, slot_f, slot_v, slot_op, crashed) numpy tables
    sliced to ``n_new`` rows, or None when the dispatch (or its
    quarantine/static check) says the caller should take its numpy
    path — never an exception, never a verdict cost."""
    from jepsen_tpu.lin import supervise
    from jepsen_tpu.obs import trace as obs_trace

    if not pack_dev_enabled():
        return None
    p = len(p_slot)
    p_pad = _pow2(p, 8)
    rows_pad = _pow2(n_new, 8)
    c_pad = _pow2(n1 + 1, 8)
    vw = op_v.shape[1]
    key = supervise.shape_key("pack-dev", cap=p_pad, window=W,
                              kernel=f"paint-{kernel}",
                              rows=rows_pad, band="stream")
    if supervise.quarantined(key) is not None:
        _dev_stats["quarantine_skips"] += 1
        _dev_stats["host_fallbacks"] += 1
        return None
    import jax.numpy as jnp

    ckey = (W, rows_pad, p_pad, c_pad, vw)
    fn = _paint_cache.get(ckey)
    if fn is None:
        import jax

        fn = jax.jit(_paint_program(W, rows_pad, vw))
        _paint_cache[ckey] = fn

    def padded(a, size, fill=0, dtype=np.int32):
        out = np.full((size,) + np.asarray(a).shape[1:], fill, dtype)
        out[:len(a)] = a
        return out

    ps = padded(np.where(np.asarray(p_slot) < 0, W, p_slot), p_pad,
                fill=W)
    r0p = padded(r0, p_pad)
    r1p = padded(r1, p_pad)
    idp = padded(ids1, p_pad)
    opf = padded(op_f, c_pad)
    opv = np.full((c_pad, vw), int(NIL), np.int32)
    opv[:len(op_v)] = op_v
    opc = padded(op_crashed, c_pad, dtype=bool)

    def thunk():
        import jax

        return jax.device_get(fn(ps, r0p, r1p, idp, opf, opv, opc,
                                 jnp.int32(n1)))

    t0 = time.perf_counter()
    with obs_trace.span("pack-dev", lanes=1, shape=f"paint-{ckey}",
                        rows=n_new) as sp:
        outcome, res = supervise.run_guarded("pack-dev", key, thunk,
                                             stats=stats)
        sp.note(outcome=outcome)
    if outcome != "ok":
        _dev_stats["host_fallbacks"] += 1
        _dev_stats["wedges" if outcome == "wedge" else
                    "faults" if outcome == "fault" else
                    "static_skips"] += 1
        return None
    grid, active, slot_f, slot_v, slot_op, crashed = (
        np.asarray(a) for a in res)
    dt = time.perf_counter() - t0
    _dev_stats["dev_packs"] += 1
    _dev_stats["dev_lanes"] += 1
    _dev_stats["dev_pack_s"] += dt
    s = np.s_[:n_new]
    return (np.ascontiguousarray(grid[s], np.int32),
            np.ascontiguousarray(active[s]),
            np.ascontiguousarray(slot_f[s], np.int32),
            np.ascontiguousarray(slot_v[s], np.int32),
            np.ascontiguousarray(slot_op[s], np.int32),
            np.ascontiguousarray(crashed[s]))
