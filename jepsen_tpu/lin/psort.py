"""Pallas in-VMEM bitonic sort-dedup for the sparse engine's packed keys.

``lax.sort`` on this TPU is stage-overhead-bound: ~2.4 ms for 64k
elements and ~2.5 ms up to ~2M — each of its O(log^2 n) compare-exchange
stages is a separate HBM-round-tripping HLO. The sparse frontier engine
(:mod:`jepsen_tpu.lin.bfs`) pays 4-6 such sorts per return event, which
made the wide-window band (windows 21..64, e.g. cockroach's
concurrency-30 registers) cost tens of ms per event.

This module runs the whole dedup — bitonic sort, adjacent-duplicate
masking, and the compaction re-sort — as ONE pallas kernel with the key
array resident in VMEM, so the ~200 stages are VPU register/VMEM ops
with no per-stage dispatch. Measured on the v5e chip (u32 keys):

=========  ==========  ============
elements   lax.sort    this kernel
=========  ==========  ============
2^16       2.4 ms      0.07 ms
2^17       2.5 ms      0.28 ms
2^18       2.6 ms      0.72 ms
2^19       2.4 ms      1.86 ms
2^20       2.6 ms      3.9 ms (lax wins past here)
=========  ==========  ============

The kernel is the semantics twin of ``bfs._dedup_keys`` (invalid flag in
bit 31, first-of-run survives, KEY_FILL padding/compaction) and is
fuzz-tested against it in ``tests/test_lin_psort.py``. Arrays larger
than :data:`PSORT_MAX_N` (or histories on non-TPU backends, unless
interpret mode is forced for tests) keep the lax.sort path.

Layout: keys reshaped ``[n/128, 128]`` u32; flat index = row*128 + lane.
Bitonic partner ``i ^ j`` for power-of-two j is a lane roll (j < 128)
or a sublane roll (j >= 128) selected by bit j of the flat index — both
native VPU data movements (``pltpu.roll`` with dynamic shifts), driven
by a fori_loop over stages so VMEM holds only ~4 live copies.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
MIN_N = 1024              # (8, 128) u32 tiling minimum
PSORT_MAX_N = 1 << 19     # above this lax.sort is faster (see table)
KEY_FILL = 0xFFFFFFFF     # plain int: used inside kernels as a literal
# Windowed-pairwise dominance distances (shared by the lax and pallas
# dom dedups so their semantics match exactly): after the (group, word)
# sort, every entry is tested against the predecessors at these offsets
# in addition to the group representative. A strict subset sorts
# numerically earlier, so predecessors are the only candidates; the
# window makes the prune near-pairwise ITERATIVELY — each closure pass
# re-dedups, and measured on the 100k partitioned history's blowup row
# the rep-only prune left 389k configs (antichain 9.3k) while rep +
# this window converges to 9.9k within a few passes. The window is
# SIZE-GATED (DOM_WINDOW_MAX_N, padded size): the roll chains at
# multi-million-cell lax dedups inside the nested-while chunk program
# kernel-faulted the chip on the 100k partitioned history in round 4,
# while the pallas kernels with the window ran clean to 2^18. Pruning
# less at the rare big-tier dedups is sound; the small-tier dedups
# that run every pass keep the frontier collapsed.
# Two distances. Round 4 found 4+ distances kernel-fault in-chunk;
# round 5 briefly widened this to 8 distances believing that lore was
# the group-cycle-orbit bug misattributed, and the widened pallas
# kernels then faulted the chip mid-history (probe_r5fc, row ~20k) —
# so the round-4 finding stands for the PALLAS kernels. (1, 2) is the
# proven-safe static window; real pruning strength comes from the
# FORCED lax path (chain scan over 1..DOM_CHAIN + iterated rounds),
# which escalation tiers and host passes always use.
DOM_WINDOW = (1, 2)
DOM_WINDOW_MAX_N = 1 << 18
# Forced-window dedups additionally run a CHAIN scan: a carried copy
# shifted by one more position each step tests every predecessor at
# distances 1..DOM_CHAIN, so in-group dominance pairs up to that span
# are caught (the static DOM_WINDOW misses all but the nearest —
# measured on the 100k partitioned history's wave, rep+(1,2) converge
# to 130k live configs where the true antichain is ~9k), and the whole
# prune+compact runs DOM_ITERS rounds so survivors compact together and
# previously-distant dominators become chain-reachable. In the lax path
# the chain is a fori of rolls; Mosaic cannot legalize that scan, so
# the pallas kernels unroll it statically (~DOM_CHAIN extra vector
# steps per round — still far cheaper than the stage-overhead-bound
# lax.sort at these sizes).
DOM_CHAIN = 128
DOM_ITERS = 2


def dom_window(n: int, force: bool = False) -> tuple:
    """The dominance window for an n-element dedup (empty past the
    size gate — see DOM_WINDOW). ``JEPSEN_TPU_DOM_WINDOW`` overrides:
    ``0`` disables the window entirely (the fault-triage escape
    hatch), any other integer replaces the max-pad EXPONENT (default
    log2(DOM_WINDOW_MAX_N)). ``force`` skips the size gate (not the
    env kill switch): host-sequenced single-pass dispatches keep the
    window engaged at capacities where the nested-while chunk programs
    fault (bfs._host_rows)."""
    env = os.environ.get("JEPSEN_TPU_DOM_WINDOW", "")
    if env == "0":
        return ()
    k = len(DOM_WINDOW)
    if ":" in env:
        env, k = env.split(":")
        k = int(k)
    if force:
        return DOM_WINDOW[:k]
    max_n = (1 << int(env)) if env else DOM_WINDOW_MAX_N
    return DOM_WINDOW[:k] if pad_size(n) <= max_n else ()


def pad_size(n: int) -> int:
    """The kernel size for an n-element dedup: next power of two, at
    least the tiling minimum."""
    return max(MIN_N, 1 << (n - 1).bit_length())


def backend_ok() -> bool:
    """True when this backend should use the in-VMEM kernel at all.
    Decided host-side and passed into the engine programs as a static
    arg, so jit cache keys reflect the routing. ``JEPSEN_TPU_PSORT=0``
    forces the lax path, ``=interpret`` forces the kernel in
    interpreter mode (CPU parity tests)."""
    mode = os.environ.get("JEPSEN_TPU_PSORT", "1")
    if mode == "0":
        return False
    return mode == "interpret" or _on_tpu()


def available(n: int) -> bool:
    """Size gate: the kernel handles n-element dedups up to
    :data:`PSORT_MAX_N` (padded); lax.sort is faster beyond."""
    return pad_size(n) <= PSORT_MAX_N


def _interpret() -> bool:
    return os.environ.get("JEPSEN_TPU_PSORT") == "interpret" or \
        not _on_tpu()


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _bitonic_sort(x, flat, lane_iota, *, S, K):
    """Full bitonic sort of x ([S, 128] u32, ascending in flat order).
    fori_loop over the K(K+1)/2 stages; partner exchange via dynamic
    lane/sublane rolls."""
    del lane_iota

    def stage(x, k, jj):
        j = jnp.uint32(1) << jj
        jl = jnp.where(jj < 7, j, 0).astype(jnp.int32)
        js = jnp.where(jj < 7, 0, j >> 7).astype(jnp.int32)
        upper = (flat & j) != 0
        p = jnp.where(
            upper,
            pltpu.roll(pltpu.roll(x, jl, 1), js, 0),
            pltpu.roll(pltpu.roll(x, (LANE - jl) % LANE, 1),
                       (S - js) % S, 0))
        desc = ((flat >> (k + 1)) & 1) == 1
        # keep x iff (x is the smaller) == (this position wants smaller)
        keep = (x < p) == (upper == desc)
        return jnp.where(keep | (x == p), x, p)

    def outer(k, x):
        def inner(t, x):
            return stage(x, jnp.uint32(k), jnp.uint32(k - t))
        return lax.fori_loop(0, k + 1, inner, x)

    return lax.fori_loop(0, K, outer, x)


def _dedup_body(key_ref, out_ref, total_ref, *, S, K):
    x = key_ref[:]
    lane = lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    row = lax.broadcasted_iota(jnp.uint32, x.shape, 0)
    flat = row * LANE + lane

    x = _bitonic_sort(x, flat, lane, S=S, K=K)

    # prev[i] = x[i-1]: lane roll +1, wrapping lane 0 to the previous
    # row's lane 127 via a sublane roll.
    a = pltpu.roll(x, 1, 1)
    prev = jnp.where(lane == 0, pltpu.roll(a, 1, 0), a)
    keep = (x >> 31 == 0) & ((flat == 0) | (x != prev))
    total_ref[0] = jnp.sum(keep.astype(jnp.int32))
    x = jnp.where(keep, x, jnp.uint32(KEY_FILL))

    out_ref[:] = _bitonic_sort(x, flat, lane, S=S, K=K)


@partial(jax.jit, static_argnames=("n_pad",))
def _dedup_call(keys, n_pad):
    S = n_pad // LANE
    K = n_pad.bit_length() - 1
    out, total = pl.pallas_call(
        partial(_dedup_body, S=S, K=K),
        name="psort_dedup",
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(keys.reshape(S, LANE))
    return out.reshape(-1), total[0]


def _assert_cap_contract(n: int, cap: int) -> None:
    """The dedup entry points promise ``keys[cap]`` outputs; that holds
    only when the padded kernel size covers cap (all engine call sites
    pass n >= cap — candidate arrays are cap*(1+M)). Enforce it so a
    future caller cannot silently break the fixed-shape lax.while_loop
    carries in bfs."""
    if pad_size(n) < cap:
        raise ValueError(
            f"psort dedup contract: pad_size({n})={pad_size(n)} < cap "
            f"{cap}; the output could not fill keys[cap]")


def _bitonic_sort2(hi, lo, flat, *, S, K):
    """Bitonic sort of (hi, lo) u32 pairs, ascending by the 64-bit
    lexicographic key. Same stage structure as _bitonic_sort with a
    pair compare-exchange."""
    def stage(hi, lo, k, jj):
        j = jnp.uint32(1) << jj
        jl = jnp.where(jj < 7, j, 0).astype(jnp.int32)
        js = jnp.where(jj < 7, 0, j >> 7).astype(jnp.int32)
        upper = (flat & j) != 0

        def partner(x):
            return jnp.where(
                upper,
                pltpu.roll(pltpu.roll(x, jl, 1), js, 0),
                pltpu.roll(pltpu.roll(x, (LANE - jl) % LANE, 1),
                           (S - js) % S, 0))

        p_hi = partner(hi)
        p_lo = partner(lo)
        desc = ((flat >> (k + 1)) & 1) == 1
        lt = (hi < p_hi) | ((hi == p_hi) & (lo < p_lo))
        eq = (hi == p_hi) & (lo == p_lo)
        keep = (lt == (upper == desc)) | eq
        return (jnp.where(keep, hi, p_hi), jnp.where(keep, lo, p_lo))

    def outer(k, c):
        def inner(t, c):
            return stage(*c, jnp.uint32(k), jnp.uint32(k - t))
        return lax.fori_loop(0, k + 1, inner, c)

    return lax.fori_loop(0, K, outer, (hi, lo))


def _dedup2_body(hi_ref, lo_ref, out_hi_ref, out_lo_ref, total_ref,
                 *, S, K):
    hi = hi_ref[:]
    lo = lo_ref[:]
    lane = lax.broadcasted_iota(jnp.uint32, hi.shape, 1)
    row = lax.broadcasted_iota(jnp.uint32, hi.shape, 0)
    flat = row * LANE + lane

    hi, lo = _bitonic_sort2(hi, lo, flat, S=S, K=K)

    def prev(x):
        a = pltpu.roll(x, 1, 1)
        return jnp.where(lane == 0, pltpu.roll(a, 1, 0), a)

    dup = (hi == prev(hi)) & (lo == prev(lo))
    keep = (hi >> 31 == 0) & ((flat == 0) | ~dup)
    total_ref[0] = jnp.sum(keep.astype(jnp.int32))
    hi = jnp.where(keep, hi, jnp.uint32(KEY_FILL))
    lo = jnp.where(keep, lo, jnp.uint32(KEY_FILL))

    out_hi_ref[:], out_lo_ref[:] = _bitonic_sort2(hi, lo, flat, S=S, K=K)


@partial(jax.jit, static_argnames=("n_pad",))
def _dedup2_call(hi, lo, n_pad):
    S = n_pad // LANE
    K = n_pad.bit_length() - 1
    out_hi, out_lo, total = pl.pallas_call(
        partial(_dedup2_body, S=S, K=K),
        name="psort_dedup2",
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={0: 0, 1: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(hi.reshape(S, LANE), lo.reshape(S, LANE))
    return out_hi.reshape(-1), out_lo.reshape(-1), total[0]


def dedup_keys2(hi, lo, valid, cap):
    """Pair-key twin of :func:`dedup_keys` for 64-bit packed configs
    (hi, lo u32; invalid flag goes into hi bit 31, so hi's payload must
    stay below 2^31). Returns (hi[cap], lo[cap], count, overflow) with
    survivors ascending by (hi, lo) and KEY_FILL padding."""
    n = hi.shape[0]
    _assert_cap_contract(n, cap)
    n_pad = pad_size(n)
    hi = hi | ((~valid).astype(jnp.uint32) << 31)
    if n_pad > n:
        pad = jnp.full(n_pad - n, KEY_FILL, jnp.uint32)
        hi = jnp.concatenate([hi, pad])
        lo = jnp.concatenate([lo, pad])
    out_hi, out_lo, total = _dedup2_call(hi, lo, n_pad)
    if out_hi.shape[0] > cap:
        out_hi = out_hi[:cap]
        out_lo = out_lo[:cap]
    overflow = total > cap
    count = jnp.minimum(total, cap)
    return out_hi, out_lo, count, overflow


def _flat_prev(x, d, S):
    """Value at flat index i-d (power-of-two d), clamped cyclically —
    callers mask position-0 effects via their start flags."""
    if d < LANE:
        a = pltpu.roll(x, d, 1)
        lane = lax.broadcasted_iota(jnp.uint32, x.shape, 1)
        return jnp.where(lane < d, pltpu.roll(a, 1, 0), a)
    return pltpu.roll(x, d // LANE, 0)


def _dedup_dom_body(masks_ref, a_ref, w_ref, out_ref, total_ref,
                    *, S, K, force=False):
    """Sort (group-part, dominance-word) pairs, drop duplicates and
    dominated entries (see bfs._dedup_keys_dom: the word packs crashed
    bits as-is and read bits complemented, so dominance is a single
    subset test), emit the recombined full keys ascending. a carries
    the invalid flag in bit 31; masks_ref = (cmask, rmask)."""
    a = a_ref[:]
    w = w_ref[:]
    cmask = masks_ref[0]
    rmask = masks_ref[1]
    lane = lax.broadcasted_iota(jnp.uint32, a.shape, 1)
    row = lax.broadcasted_iota(jnp.uint32, a.shape, 0)
    flat = row * LANE + lane
    first = flat == 0

    a, w = _bitonic_sort2(a, w, flat, S=S, K=K)
    keep = first
    for round_ in range(DOM_ITERS if force else 1):
        if round_:
            # Compact survivors (order-preserving re-sort of
            # FILL-masked pairs) so distant dominators become
            # chain-reachable — lax twin: bfs._dedup_keys_dom rounds.
            fill = jnp.uint32(KEY_FILL)
            a = jnp.where(keep, a, fill)
            w = jnp.where(keep, w, fill)
            a, w = _bitonic_sort2(a, w, flat, S=S, K=K)
        pa = _flat_prev(a, 1, S)
        dup = (a == pa) & (w == _flat_prev(w, 1, S)) & ~first
        start = first | (a != pa)
        # Segmented broadcast of each group's representative word (the
        # scan runs on u32 flags: bool-vector rolls don't reliably
        # lower).
        f = w
        done = start.astype(jnp.uint32)
        d = 1
        while d < (1 << K):
            f = jnp.where(done != 0, f, _flat_prev(f, d, S))
            done = done | _flat_prev(done, d, S)
            d <<= 1
        dominated = ((f & ~w) == 0) & (w != f)
        for dd in dom_window(S * LANE, force):
            a_d = _flat_prev(a, dd, S)
            w_d = _flat_prev(w, dd, S)
            dominated = dominated | (
                (flat >= dd) & (a_d == a) & ((w_d & ~w) == 0)
                & (w_d != w))
        if force:
            # Statically-unrolled chain scan over distances
            # 1..DOM_CHAIN (Mosaic cannot legalize the fori the lax
            # twin uses).
            ra, rw = a, w
            for dd in range(1, DOM_CHAIN + 1):
                ra = _flat_prev(ra, 1, S)
                rw = _flat_prev(rw, 1, S)
                dominated = dominated | (
                    (flat >= dd) & (ra == a) & ((rw & ~w) == 0)
                    & (rw != w))
        keep = (a >> 31 == 0) & ~dup & ~dominated
    total_ref[0] = jnp.sum(keep.astype(jnp.int32))
    full = jnp.where(
        keep,
        (a & jnp.uint32(0x7FFFFFFF)) | (w & cmask) | ((~w) & rmask),
        jnp.uint32(KEY_FILL))
    out_ref[:] = _bitonic_sort(full, flat, lane, S=S, K=K)


@partial(jax.jit, static_argnames=("n_pad", "force"))
def _dedup_dom_call(a, w, cmask, rmask, n_pad, force=False):
    S = n_pad // LANE
    K = n_pad.bit_length() - 1
    masks = jnp.stack([cmask, rmask]).astype(jnp.uint32)
    out, total = pl.pallas_call(
        partial(_dedup_dom_body, S=S, K=K, force=force),
        name="psort_dedup_dom",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(masks, a.reshape(S, LANE), w.reshape(S, LANE))
    return out.reshape(-1), total[0]


def _assert_force_window_interpret_only(force_window: bool) -> None:
    """``force_window=True`` (the statically-unrolled DOM_CHAIN scan +
    iterated prune rounds) exists ONLY for interpret-mode parity tests
    against the lax chain path. On the real Mosaic backend it is
    compile-pathological (the unrolled 128-distance chain takes 20+
    minutes to compile) and FAULTED the chip mid-history in both
    round-5 runs that enabled it (probe_r5fc/fd, rows ~13-20k) — every
    production crash-dom call site therefore hard-codes the forced lax
    path (bfs._dedup_keys_dom/_dedup_keys2_dom with dom_force=True).
    Fail fast so a future caller cannot silently re-enter the
    known-unstable path."""
    if force_window and not _interpret():
        raise RuntimeError(
            "psort force_window dominance dedup must not run on the "
            "real Mosaic backend: it is compile-pathological and "
            "faulted the chip in both round-5 runs that enabled "
            "it; use the forced-lax chain path "
            "(bfs._dedup_keys_dom/_dedup_keys2_dom with "
            "dom_force=True) instead")


def dedup_keys_dom(a, w, cmask, rmask, cap, force_window=False):
    """In-VMEM twin of the lax path in ``bfs._dedup_keys_dom``. ``a`` is
    the group part (mutator bits + state) with the invalid flag already
    in bit 31; ``w`` the packed dominance word (crashed bits | inverted
    read bits); ``cmask``/``rmask`` u32 scalars for recombination.
    Returns (keys[cap] full-key ascending, count, overflow).

    ``force_window=True`` is interpret-mode-only (parity tests): see
    :func:`_assert_force_window_interpret_only`."""
    n = a.shape[0]
    _assert_force_window_interpret_only(force_window)
    _assert_cap_contract(n, cap)
    n_pad = pad_size(n)
    if n_pad > n:
        pad = jnp.full(n_pad - n, KEY_FILL, jnp.uint32)
        a = jnp.concatenate([a, pad])
        w = jnp.concatenate([w, jnp.zeros(n_pad - n, jnp.uint32)])
    out, total = _dedup_dom_call(a, w, cmask, rmask, n_pad,
                                 force=force_window)
    if out.shape[0] > cap:
        out = out[:cap]
    return out, jnp.minimum(total, cap), total > cap


def _bitonic_sort4(a, b, c, d, flat, *, S, K):
    """Bitonic sort of (a, b, c, d) u32 quads, ascending by the 128-bit
    lexicographic key. Same stage structure as _bitonic_sort2 with a
    4-word compare-exchange."""
    def stage(a, b, c, d, k, jj):
        j = jnp.uint32(1) << jj
        jl = jnp.where(jj < 7, j, 0).astype(jnp.int32)
        js = jnp.where(jj < 7, 0, j >> 7).astype(jnp.int32)
        upper = (flat & j) != 0

        def partner(x):
            return jnp.where(
                upper,
                pltpu.roll(pltpu.roll(x, jl, 1), js, 0),
                pltpu.roll(pltpu.roll(x, (LANE - jl) % LANE, 1),
                           (S - js) % S, 0))

        pa, pb, pc, pd = partner(a), partner(b), partner(c), partner(d)
        desc = ((flat >> (k + 1)) & 1) == 1
        lt = (a < pa) | ((a == pa) & (
            (b < pb) | ((b == pb) & (
                (c < pc) | ((c == pc) & (d < pd))))))
        eq = (a == pa) & (b == pb) & (c == pc) & (d == pd)
        keep = (lt == (upper == desc)) | eq
        return (jnp.where(keep, a, pa), jnp.where(keep, b, pb),
                jnp.where(keep, c, pc), jnp.where(keep, d, pd))

    def outer(k, q):
        def inner(t, q):
            return stage(*q, jnp.uint32(k), jnp.uint32(k - t))
        return lax.fori_loop(0, k + 1, inner, q)

    return lax.fori_loop(0, K, outer, (a, b, c, d))


def _dedup2_dom_body(masks_ref, a_hi_ref, a_lo_ref, w_hi_ref, w_lo_ref,
                     out_hi_ref, out_lo_ref, total_ref, *, S, K,
                     force=False):
    """Pair-key twin of _dedup_dom_body (see bfs._dedup_keys2_dom): sort
    by (group pair, dominance-word pair), drop duplicates and dominated
    entries, emit recombined full keys ascending by (hi, lo). masks_ref
    = (cmask_hi, cmask_lo, rmask_hi, rmask_lo)."""
    a_hi = a_hi_ref[:]
    a_lo = a_lo_ref[:]
    w_hi = w_hi_ref[:]
    w_lo = w_lo_ref[:]
    cmask_hi = masks_ref[0]
    cmask_lo = masks_ref[1]
    rmask_hi = masks_ref[2]
    rmask_lo = masks_ref[3]
    lane = lax.broadcasted_iota(jnp.uint32, a_hi.shape, 1)
    row = lax.broadcasted_iota(jnp.uint32, a_hi.shape, 0)
    flat = row * LANE + lane

    first = flat == 0
    a_hi, a_lo, w_hi, w_lo = _bitonic_sort4(a_hi, a_lo, w_hi, w_lo,
                                            flat, S=S, K=K)
    keep = first
    for round_ in range(DOM_ITERS if force else 1):
        if round_:
            # Order-preserving compaction between rounds (see
            # _dedup_dom_body).
            fill = jnp.uint32(KEY_FILL)
            a_hi = jnp.where(keep, a_hi, fill)
            a_lo = jnp.where(keep, a_lo, fill)
            w_hi = jnp.where(keep, w_hi, fill)
            w_lo = jnp.where(keep, w_lo, fill)
            a_hi, a_lo, w_hi, w_lo = _bitonic_sort4(
                a_hi, a_lo, w_hi, w_lo, flat, S=S, K=K)
        pah = _flat_prev(a_hi, 1, S)
        pal = _flat_prev(a_lo, 1, S)
        same_a = (a_hi == pah) & (a_lo == pal)
        dup = same_a & (w_hi == _flat_prev(w_hi, 1, S)) & \
            (w_lo == _flat_prev(w_lo, 1, S)) & ~first
        start = first | ~same_a
        fh = w_hi
        fl = w_lo
        done = start.astype(jnp.uint32)
        d = 1
        while d < (1 << K):
            fh = jnp.where(done != 0, fh, _flat_prev(fh, d, S))
            fl = jnp.where(done != 0, fl, _flat_prev(fl, d, S))
            done = done | _flat_prev(done, d, S)
            d <<= 1
        dominated = ((fh & ~w_hi) == 0) & ((fl & ~w_lo) == 0) & \
            ~((w_hi == fh) & (w_lo == fl))
        for dd in dom_window(S * LANE, force):
            ah_d = _flat_prev(a_hi, dd, S)
            al_d = _flat_prev(a_lo, dd, S)
            wh_d = _flat_prev(w_hi, dd, S)
            wl_d = _flat_prev(w_lo, dd, S)
            dominated = dominated | (
                (flat >= dd) & (ah_d == a_hi) & (al_d == a_lo)
                & ((wh_d & ~w_hi) == 0) & ((wl_d & ~w_lo) == 0)
                & ~((wh_d == w_hi) & (wl_d == w_lo)))
        if force:
            # Statically-unrolled chain scan (see _dedup_dom_body).
            rah, ral, rwh, rwl = a_hi, a_lo, w_hi, w_lo
            for dd in range(1, DOM_CHAIN + 1):
                rah = _flat_prev(rah, 1, S)
                ral = _flat_prev(ral, 1, S)
                rwh = _flat_prev(rwh, 1, S)
                rwl = _flat_prev(rwl, 1, S)
                dominated = dominated | (
                    (flat >= dd) & (rah == a_hi) & (ral == a_lo)
                    & ((rwh & ~w_hi) == 0) & ((rwl & ~w_lo) == 0)
                    & ~((rwh == w_hi) & (rwl == w_lo)))
        keep = (a_hi >> 31 == 0) & ~dup & ~dominated
    total_ref[0] = jnp.sum(keep.astype(jnp.int32))
    full_hi = jnp.where(
        keep,
        (a_hi & jnp.uint32(0x7FFFFFFF)) | (w_hi & cmask_hi)
        | ((~w_hi) & rmask_hi),
        jnp.uint32(KEY_FILL))
    full_lo = jnp.where(
        keep, a_lo | (w_lo & cmask_lo) | ((~w_lo) & rmask_lo),
        jnp.uint32(KEY_FILL))
    out_hi_ref[:], out_lo_ref[:] = _bitonic_sort2(full_hi, full_lo,
                                                  flat, S=S, K=K)


@partial(jax.jit, static_argnames=("n_pad", "force"))
def _dedup2_dom_call(a_hi, a_lo, w_hi, w_lo, masks, n_pad, force=False):
    S = n_pad // LANE
    K = n_pad.bit_length() - 1
    out_hi, out_lo, total = pl.pallas_call(
        partial(_dedup2_dom_body, S=S, K=K, force=force),
        name="psort_dedup2_dom",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 4,
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(masks, a_hi.reshape(S, LANE), a_lo.reshape(S, LANE),
      w_hi.reshape(S, LANE), w_lo.reshape(S, LANE))
    return out_hi.reshape(-1), out_lo.reshape(-1), total[0]


def dedup_keys2_dom(a_hi, a_lo, w_hi, w_lo, cmask_hi, cmask_lo,
                    rmask_hi, rmask_lo, cap, force_window=False):
    """In-VMEM twin of the lax path in ``bfs._dedup_keys2_dom``. ``a``
    pair carries group bits (invalid flag already in a_hi bit 31), ``w``
    pair the packed dominance words. Returns (hi[cap], lo[cap], count,
    overflow), survivors full-key ascending by (hi, lo).

    ``force_window=True`` is interpret-mode-only (parity tests): see
    :func:`_assert_force_window_interpret_only`."""
    n = a_hi.shape[0]
    _assert_force_window_interpret_only(force_window)
    _assert_cap_contract(n, cap)
    n_pad = pad_size(n)
    if n_pad > n:
        pad = jnp.full(n_pad - n, KEY_FILL, jnp.uint32)
        zpad = jnp.zeros(n_pad - n, jnp.uint32)
        a_hi = jnp.concatenate([a_hi, pad])
        a_lo = jnp.concatenate([a_lo, pad])
        w_hi = jnp.concatenate([w_hi, zpad])
        w_lo = jnp.concatenate([w_lo, zpad])
    masks = jnp.stack([cmask_hi, cmask_lo, rmask_hi, rmask_lo]) \
        .astype(jnp.uint32)
    out_hi, out_lo, total = _dedup2_dom_call(a_hi, a_lo, w_hi, w_lo,
                                             masks, n_pad,
                                             force=force_window)
    if out_hi.shape[0] > cap:
        out_hi = out_hi[:cap]
        out_lo = out_lo[:cap]
    return out_hi, out_lo, jnp.minimum(total, cap), total > cap


def _compact_body(key_ref, out_ref, total_ref, *, S, K):
    """Compaction-only kernel: callers have already masked dropped
    entries to KEY_FILL and guarantee survivors are DISTINCT (the
    return-event filter drops the same held bit from every survivor —
    injective), so one bitonic sort packs survivors ascending."""
    x = key_ref[:]
    lane = lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    row = lax.broadcasted_iota(jnp.uint32, x.shape, 0)
    flat = row * LANE + lane
    total_ref[0] = jnp.sum((x != jnp.uint32(KEY_FILL)).astype(jnp.int32))
    out_ref[:] = _bitonic_sort(x, flat, lane, S=S, K=K)


@partial(jax.jit, static_argnames=("n_pad",))
def _compact_call(keys, n_pad):
    S = n_pad // LANE
    K = n_pad.bit_length() - 1
    out, total = pl.pallas_call(
        partial(_compact_body, S=S, K=K),
        name="psort_compact",
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(keys.reshape(S, LANE))
    return out.reshape(-1), total[0]


def compact_keys(keys, cap):
    """Pack the non-KEY_FILL entries of ``keys`` (distinct by caller
    contract) to an ascending prefix. Returns (keys[cap], count)."""
    n = keys.shape[0]
    _assert_cap_contract(n, cap)
    n_pad = pad_size(n)
    if n_pad > n:
        keys = jnp.concatenate(
            [keys, jnp.full(n_pad - n, KEY_FILL, jnp.uint32)])
    out, total = _compact_call(keys, n_pad)
    return out[:cap], jnp.minimum(total, cap)


def _compact2_body(hi_ref, lo_ref, out_hi_ref, out_lo_ref, total_ref,
                   *, S, K):
    hi = hi_ref[:]
    lo = lo_ref[:]
    lane = lax.broadcasted_iota(jnp.uint32, hi.shape, 1)
    row = lax.broadcasted_iota(jnp.uint32, hi.shape, 0)
    flat = row * LANE + lane
    live = (hi != jnp.uint32(KEY_FILL)) | (lo != jnp.uint32(KEY_FILL))
    total_ref[0] = jnp.sum(live.astype(jnp.int32))
    out_hi_ref[:], out_lo_ref[:] = _bitonic_sort2(hi, lo, flat, S=S, K=K)


@partial(jax.jit, static_argnames=("n_pad",))
def _compact2_call(hi, lo, n_pad):
    S = n_pad // LANE
    K = n_pad.bit_length() - 1
    out_hi, out_lo, total = pl.pallas_call(
        partial(_compact2_body, S=S, K=K),
        name="psort_compact2",
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((S, LANE), jnp.uint32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={0: 0, 1: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(hi.reshape(S, LANE), lo.reshape(S, LANE))
    return out_hi.reshape(-1), out_lo.reshape(-1), total[0]


def compact_keys2(hi, lo, cap):
    """Pair twin of :func:`compact_keys`: dropped entries are KEY_FILL
    in BOTH words; survivors distinct. Returns (hi[cap], lo[cap],
    count)."""
    n = hi.shape[0]
    _assert_cap_contract(n, cap)
    n_pad = pad_size(n)
    if n_pad > n:
        pad = jnp.full(n_pad - n, KEY_FILL, jnp.uint32)
        hi = jnp.concatenate([hi, pad])
        lo = jnp.concatenate([lo, pad])
    out_hi, out_lo, total = _compact2_call(hi, lo, n_pad)
    return out_hi[:cap], out_lo[:cap], jnp.minimum(total, cap)


def dedup_keys(key, valid, cap):
    """In-VMEM twin of ``bfs._dedup_keys``: single-u32-key sort-dedup
    (invalid flag in bit 31) with sort-based compaction, in one pallas
    kernel. Returns (keys[cap] ascending + KEY_FILL padding, count,
    overflow). Caller must have checked :func:`available`."""
    n = key.shape[0]
    _assert_cap_contract(n, cap)
    n_pad = pad_size(n)
    key = key | ((~valid).astype(jnp.uint32) << 31)
    if n_pad > n:
        key = jnp.concatenate(
            [key, jnp.full(n_pad - n, KEY_FILL, jnp.uint32)])
    out, total = _dedup_call(key, n_pad)
    if out.shape[0] > cap:
        out = out[:cap]
    overflow = total > cap
    count = jnp.minimum(total, cap)
    return out, count, overflow
