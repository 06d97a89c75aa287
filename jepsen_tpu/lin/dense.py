"""Dense config-space bitmap engine for the linearizability search.

The sparse engine (:mod:`jepsen_tpu.lin.bfs`) keeps the frontier as a
compacted list of (bitset, state) configs and pays a sort-dedup per step.
This module exploits a fact about the search space itself: with the
slot-compressed window W (:mod:`jepsen_tpu.lin.prepare`) and a single-word
model state of NS <= 32 reachable values, the ENTIRE config space has just
``2**W * NS`` points — so instead of deduplicating a list we represent the
frontier as its characteristic function, a ``uint32[2**W]`` bitmap::

    bit s of word B  ==  config (linearized-bitset B, state s) reachable

On this representation the whole just-in-time linearization closure
(reference semantics: knossos.linear / knossos.wgl, raced at
checker.clj:90-93) becomes branchless word-parallel bit algebra:

- *Linearize pending op in slot j*: rows with bit j clear contribute to
  rows with bit j set — a masked static shift of the bitmap by ``2**j``
  words, with the state transition applied as per-state-bit shifts through
  the op's transition table. No sort, no dedup (the bitmap IS the set), no
  capacity, no overflow, and therefore no cap escalation or host syncs.
- *Return of slot s*: keep rows with bit s, clear it — one masked shift.
- *Crashed (:info) ops* need no special machinery at all. They simply keep
  their slot bit forever; the 2^crashes subset blowup that inflates a list
  frontier is just... the bitmap, whose size is fixed up front. The sparse
  path's dominance-pruning join (the round-1 TPU kernel-faulter) has no
  dense analogue because nothing ever needs pruning.

The search is a `lax.while_loop` over return events inside chunked
dispatches; the host's only blocking fetch per chunk is the one-bit dead
flag (~13 round-trips for a 100k-op history). Entry-frontier snapshots
per chunk (a few KB each) can be retained via
``check_packed(snapshots=[...])`` so a counterexample pass can replay
just the failing tail on the CPU oracle (see :func:`decode_bitmap`).

Cost model: one closure pass is ``W * NS`` fused elementwise ops over
``2**W`` words. For the flagship 100k-op crashed-op history (W=15, NS~8)
that is ~4M word-ops per pass — microseconds on a TPU's vector units — and
the whole check is a handful of device programs with zero host round-trips,
vs. the reference's JVM graph search with a 32 GB heap
(jepsen/project.clj:22-25).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jepsen_tpu import util
from jepsen_tpu.lin.prepare import PackedHistory
from jepsen_tpu.obs import trace as obs_trace

# Largest window the dense representation will take: 2**20 words = 4 MiB
# bitmaps (x2 transient for the shift) — far below HBM, compile-bounded.
MAX_DENSE_WINDOW = 20
# States must fit one u32 word of bitmap per bitset row.
MAX_DENSE_STATES = 32
# Per-chunk fixed costs (table upload over the host link, dispatch)
# dominate at small chunks: measured on a v5e chip, 100k ops run at
# 42k/70k/102k/118k ops/s for chunks of 4k/8k/16k/32k. 16k balances
# throughput against the witness tail-replay window (one chunk).
CHUNK = 16384

_W_BUCKETS = (4, 6, 8, 10, 12, 14, 16, 18, 20)
_NS_BUCKETS = (4, 8, 16, 32)

def plan(p: PackedHistory):
    """Dense-searchability test. Returns ``(w, ns, nil_id, init_id)`` with
    bucketed w/ns, or None when this history needs the sparse engine."""
    from jepsen_tpu.models.kernels import (PACKED_STATE_KERNELS,
                                           packed_state_bound)

    if p.kernel is None or p.kernel.name not in PACKED_STATE_KERNELS:
        return None
    if p.state_width != 1 or p.window > MAX_DENSE_WINDOW:
        return None
    from jepsen_tpu.models.kernels import NIL

    nid = packed_state_bound(p.kernel, len(p.unintern))
    if nid + 1 > MAX_DENSE_STATES:
        return None
    w = next(b for b in _W_BUCKETS if b >= p.window)
    ns = next(b for b in _NS_BUCKETS if b >= nid + 1)
    init = int(p.init_state[0])
    init_id = nid if init == int(NIL) else init
    return w, ns, nid, init_id


def transition_tables(slot_f, slot_v, active, nil_id, *, ns, step_fn):
    """Per-(row, slot, state) transition tables from the model step
    kernel: ok[CH,w,ns] legality, to[CH,w,ns] successor state id (u32).
    One triple-vmap evaluates every transition a chunk can ever take in
    one shot. Inactive slots never linearize, and padded state ids past
    nil_id are masked inert. Shared by the XLA and pallas backends so
    the table semantics cannot diverge between them."""
    from jepsen_tpu.models.kernels import NIL

    sid = jnp.arange(ns, dtype=jnp.int32)
    states = jnp.where(sid == nil_id, NIL, sid)[:, None]     # [ns, 1]
    per_state = jax.vmap(step_fn, in_axes=(0, None, None))
    per_slot = jax.vmap(per_state, in_axes=(None, 0, 0))
    per_row = jax.vmap(per_slot, in_axes=(None, 0, 0))
    ok, new = per_row(states, slot_f, slot_v)
    to = jnp.where(new[..., 0] == NIL, nil_id, new[..., 0])
    to = jnp.clip(to, 0, ns - 1).astype(jnp.uint32)
    ok = ok & active[:, :, None] & (sid[None, None, :] <= nil_id)
    return ok, to


@partial(jax.jit, static_argnames=("w", "ns", "step_fn"))
def _dense_chunk(F, n_rows, nil_id, ret_slot, active, slot_f, slot_v,
                 *, w, ns, step_fn):
    """Advance the frontier bitmap through up to n_rows return events.

    F: u32[2**w]; ret_slot: i32[CH]; active: bool[CH,w];
    slot_f: i32[CH,w]; slot_v: i32[CH,w,VW]. Rows past n_rows ignored.
    Returns (F, rows_done, dead, trunc) — dead means the frontier
    emptied while filtering row rows_done-1, i.e. the history is not
    linearizable; trunc means a closure hit the w+2 pass ceiling with
    changes still pending (provably impossible for this monotone
    closure — the honest-overflow channel the round-5 invariant
    demands, so a hypothetical non-monotone edit can never ship a
    silently truncated frontier as a verdict).
    """
    n_words = 1 << w
    iota = lax.iota(jnp.uint32, n_words)

    ok, to = transition_tables(slot_f, slot_v, active, nil_id,
                               ns=ns, step_fn=step_fn)

    def row_body(carry):
        r, F, dead, trunc = carry
        ok_r = ok[r]                                          # [w, ns]
        to_r = to[r]                                          # [w, ns]

        def closure_pass(F):
            for j in range(w):
                # View the B axis as [.., bit j, 2**j]: index 0 along the
                # middle axis is exactly the rows with slot j unlinearized,
                # so "linearize j" is a half-size transform + a static
                # concatenate — no roll, no mask, half the words touched.
                # (A slot-batched gather/reduce formulation of this pass
                # kernel-faults the TPU runtime in this image; the
                # reshape/concat form is the one XLA handles robustly.)
                F3 = F.reshape(-1, 2, 1 << j)
                src = F3[:, 0, :]
                contrib = jnp.zeros_like(src)
                for s in range(ns):
                    bit = (src >> s) & jnp.uint32(1)
                    contrib = contrib | jnp.where(
                        ok_r[j, s], bit << to_r[j, s], jnp.uint32(0))
                hi = F3[:, 1, :] | contrib
                F = jnp.concatenate([F3[:, :1, :], hi[:, None, :]],
                                    axis=1).reshape(F.shape)
            return F

        def closure_body(c):
            F, _, it = c
            return closure_pass(F), F, it + 1

        # Do-while to fixpoint: the candidate pool includes the current
        # frontier (OR-accumulation), so the set is monotone and the loop
        # terminates in at most W+1 passes. The w+2 pass ceiling can
        # therefore never bind — it exists for the post-round-5
        # every-loop-carries-a-ceiling invariant (analysis/jaxpr_lint's
        # unbounded-while rule); exiting at the ceiling with changes
        # still pending flags ``trunc``, an HONEST overflow a caller
        # must turn into an unknown verdict, never a silently
        # incomplete frontier.
        F, F_prev, _ = lax.while_loop(
            lambda c: jnp.any(c[0] != c[1]) & (c[2] < w + 2),
            closure_body, closure_body((F, F, jnp.int32(0))))
        trunc = trunc | jnp.any(F != F_prev)

        # Return filter: the returner's linearization point must precede
        # its return; then recycle its slot bit. Rows without bit s wrap to
        # rows with it and contribute zero, so one masked roll does both.
        s = ret_slot[r]
        keep = jnp.where((iota >> s.astype(jnp.uint32)) & 1 == 1,
                         F, jnp.uint32(0))
        F = jnp.roll(keep, -(jnp.int32(1) << s))
        return r + 1, F, ~jnp.any(F != 0), trunc

    def row_cond(carry):
        r, _, dead, trunc = carry
        return (r < n_rows) & ~dead & ~trunc

    r, F, dead, trunc = lax.while_loop(
        row_cond, row_body,
        (jnp.int32(0), F, jnp.bool_(False), jnp.bool_(False)))
    return F, r, dead, trunc


def check_packed(p: PackedHistory, chunk: int = CHUNK, cancel=None,
                 snapshots: list | None = None, explain: bool = False,
                 backend: str = "auto") -> dict:
    """Decide linearizability of a packed history with the dense engine.

    The frontier carry chains device-side between chunk dispatches; the
    host's only blocking fetch per chunk is the one-bit dead flag, giving
    early exit on invalid histories and prompt race cancellation.
    ``snapshots``, if a list, receives ``(base_row, entry_bitmap)`` pairs
    (device arrays) for witness reconstruction; ``explain=True`` retains
    them internally and, on an invalid verdict, replays the failing tail
    on the CPU oracle to emit knossos-style configs + final-paths
    (:mod:`jepsen_tpu.lin.witness`). ``cancel`` (threading.Event) stops
    between dispatches.

    ``backend``: "pallas" runs the chunk loop as a TPU kernel with the
    bitmap resident in VMEM (:mod:`jepsen_tpu.lin.dense_pallas`;
    interpreted off-TPU), "xla" the lax.while_loop formulation, "auto"
    pallas on TPU-class hardware when the window fits, xla otherwise.
    """
    # dense.prep: host work while the device idles (set-up here, then
    # each chunk's tables and enqueue); dense.wait: the host blocked on
    # the device (doc/observability.md).
    with obs_trace.span("dense.prep"):
        pl = plan(p)
        if pl is None:
            return {"valid?": "unknown", "analyzer": "tpu-dense",
                    "error": "history outside dense engine bounds"}
        w, ns, nil_id, init_id = pl
        # Explicit callers get every chunk-entry snapshot; internal
        # explain only ever replays from the LAST one (the dead row is
        # always inside the current chunk), so retain just that and
        # keep HBM flat.
        keep_all = snapshots is not None
        if explain and snapshots is None:
            snapshots = []

        if backend not in ("auto", "xla", "pallas"):
            raise ValueError(f"unknown dense backend {backend!r}")
        use_pallas = False
        interpret = False
        dp = None
        if backend in ("auto", "pallas"):
            from jepsen_tpu.lin import dense_pallas as dp

            fits = dp.supported_w(w) is not None
            on_tpu = jax.devices()[0].platform == "tpu"
            interpret = not on_tpu
            if backend == "pallas":
                if not fits:
                    raise ValueError(
                        f"window {w} exceeds the pallas kernel bound "
                        f"{dp.MAX_PALLAS_W}; use backend='xla'")
                use_pallas = True
            else:
                use_pallas = fits and on_tpu
        if p.R == 0:
            return {"valid?": True, "analyzer": "tpu-dense", "configs": []}

        from jepsen_tpu.lin.bfs import _chunk_slice

        step_fn = p.kernel.step
        ret_slot_h = np.asarray(p.ret_slot)
        active_h = np.asarray(p.active)
        slot_f_h = np.asarray(p.slot_f)
        slot_v_h = np.asarray(p.slot_v)
        W = p.window

        # Slot indices grow monotonically (freed slots are reused
        # low-first, crashed slots accumulate upward), so early chunks
        # run on an exponentially smaller bitmap: per-chunk width = that
        # chunk's highest active slot, bucketed. Growing between chunks
        # is a zero-pad of F.
        row_hi = np.where(active_h.any(axis=1),
                          W - np.argmax(active_h[:, ::-1], axis=1), 1)

        def bucket_w(need):
            return next(b for b in _W_BUCKETS if b >= need)

        def pad_w(a, wc):
            if a.shape[1] > wc:  # slots above wc are inactive this chunk
                return a[:, :wc]
            if a.shape[1] == wc:
                return a
            pad = [(0, 0)] * a.ndim
            pad[1] = (0, wc - a.shape[1])
            return np.pad(a, pad)

        def eng_w(need: int) -> int:
            wc = bucket_w(need)
            return dp.supported_w(wc) if use_pallas else wc

        w_cur = eng_w(int(row_hi[:min(chunk, p.R)].max()))
        F = jnp.zeros(1 << w_cur, jnp.uint32).at[0].set(
            jnp.uint32(1) << init_id)
    site = "dense-pallas" if use_pallas else "dense-chunk"

    # One blocking fetch (the dead flag) per chunk: chunks are strictly
    # sequential so there is no pipelining to lose, it exits early on a
    # dead frontier, and it keeps a competition-race cancel prompt. For
    # the flagship 100k history that is ~13 round-trips total (the round-1
    # sparse engine paid ~196).
    base = 0
    while base < p.R:
        if cancel is not None and cancel.is_set():
            return {"valid?": "unknown", "analyzer": "tpu-dense",
                    "error": "cancelled"}
        n = min(chunk, p.R - base)
        with obs_trace.span("dispatch", site=site, rows=int(n)) as dsp:
            with obs_trace.span("dense.prep"):
                w_c = eng_w(int(row_hi[base:base + n].max()))
                if w_c > w_cur:
                    F = jnp.pad(F, (0, (1 << w_c) - (1 << w_cur)))
                    w_cur = w_c
                if snapshots is not None:
                    if keep_all:
                        snapshots.append((base, F))
                    else:
                        snapshots[:] = [(base, F)]
                if use_pallas:
                    # Two grid sizes a width: the chunk, and an eighth
                    # of it for a chunk of no more rows (short histories
                    # keep small tables). Each size is one more kernel to
                    # trace, lower and compile in set-up, seconds on the
                    # chip; a padded block past n only fetches its
                    # tables.
                    n_pad = chunk if 8 * n > chunk else -(-chunk // 8)
                    sl = lambda a: _chunk_slice(  # noqa: E731
                        a, base, chunk)[:n_pad]
                    masks = dp.transition_masks(
                        jnp.asarray(pad_w(sl(slot_f_h), w_cur)),
                        jnp.asarray(pad_w(sl(slot_v_h), w_cur)),
                        jnp.asarray(pad_w(sl(active_h), w_cur)),
                        jnp.int32(nil_id), ns=ns, step_fn=step_fn)
                    F, r_done, dead = dp.pallas_chunk(
                        F, jnp.int32(n), masks, jnp.asarray(sl(ret_slot_h)),
                        w=w_cur, ns=ns, chunk=n_pad, interpret=interpret)
                    rb = dp.block_rows(n_pad)
                    # The pallas closure runs to true fixpoint (its
                    # waived unbounded loop) — no truncation channel to
                    # consult.
                    trunc = jnp.bool_(False)
                else:
                    F, r_done, dead, trunc = _dense_chunk(
                        F, jnp.int32(n), jnp.int32(nil_id),
                        jnp.asarray(_chunk_slice(ret_slot_h, base, chunk)),
                        jnp.asarray(pad_w(_chunk_slice(active_h, base,
                                                       chunk), w_cur)),
                        jnp.asarray(pad_w(_chunk_slice(slot_f_h, base,
                                                       chunk), w_cur)),
                        jnp.asarray(pad_w(_chunk_slice(slot_v_h, base,
                                                       chunk), w_cur)),
                        w=w_cur, ns=ns, step_fn=step_fn)
            util.progress_tick()   # liveness: one tick per decided chunk
            # ONE blocking transfer carries both flags (the per-chunk
            # fetch budget this engine's cost model is built on).
            with obs_trace.span("dense.wait"):
                flags = np.asarray(jnp.stack([dead, trunc]))
            dsp.note(outcome="ok")
        dead_b, trunc_b = bool(flags[0]), bool(flags[1])
        obs_trace.count("dense.chunks")
        if trunc_b:
            # The closure ceiling fired with changes pending: the
            # frontier is incomplete, so neither a dead nor a live
            # result is trustworthy — honest unknown (round-5
            # invariant; provably unreachable for the monotone
            # closure).
            return {"valid?": "unknown", "analyzer": "tpu-dense",
                    "backend": "pallas" if use_pallas else "xla",
                    "overflow": "budget",
                    "error": f"dense closure pass ceiling hit with "
                             f"changes pending near row {base} "
                             f"(non-monotone closure edit?)"}
        if dead_b:
            r_done = int(r_done)
            obs_trace.count("dense.rows", r_done)
            if use_pallas:
                obs_trace.count("dense.grid_steps", -(-r_done // rb))
            r = base + r_done - 1
            ret = p.ops[int(p.ret_op[r])]
            out = {"valid?": False, "analyzer": "tpu-dense",
                   "backend": "pallas" if use_pallas else "xla",
                   "dead-row": r,
                   "op": {"process": ret.process, "f": ret.f,
                          "value": ret.value, "index": ret.op_index,
                          "ok": ret.ok},
                   "configs": [], "final-paths": []}
            if explain and snapshots and \
                    not (cancel is not None and cancel.is_set()):
                from jepsen_tpu.lin import witness

                out.update(witness.tail_replay(p, nil_id, snapshots, r,
                                               cancel=cancel))
            return out
        obs_trace.count("dense.rows", n)
        if use_pallas:
            # Grid steps that swept rows; the padding steps past n,
            # which fetch their block and skip its rows, are left out.
            obs_trace.count("dense.grid_steps", -(-n // rb))
        base += n

    with obs_trace.span("dense.wait"):
        popcount = int(jnp.sum(lax.population_count(F)))
    return {"valid?": True, "analyzer": "tpu-dense",
            "backend": "pallas" if use_pallas else "xla",
            "final-frontier-popcount": popcount,
            "configs": []}


def decode_bitmap(F, nil_id: int) -> list[tuple[int, tuple]]:
    """Host-side decode of a frontier bitmap into (bitset, state-word)
    configs in the CPU oracle's representation (state NIL-restored)."""
    from jepsen_tpu.models.kernels import NIL

    F = np.asarray(F)
    out = []
    for B in np.nonzero(F)[0]:
        word = int(F[B])
        s = 0
        while word:
            if word & 1:
                sv = int(NIL) if s == nil_id else s
                out.append((int(B), (sv,)))
            word >>= 1
            s += 1
    return out
