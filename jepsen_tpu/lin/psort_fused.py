"""Fused Pallas expand+dedup CLOSURE FIXPOINT for the compact register
band (the pass-chain half of the host round-trip cut).

The sparse engine's just-in-time closure runs as a chain of passes —
expand candidates, sort-dedup, test the fixpoint — and even with the
in-VMEM psort dedup each pass round-trips the cap*(1+M) candidate
array through HBM and pays the stage-overhead floor of its XLA
neighbours (~2.4 ms per lax.sort-sized stage, CLAUDE.md). This module
runs the WHOLE fixpoint as ONE pallas kernel: the frontier stays
resident in VMEM across passes, expansion is per-column bit algebra
driven by host-precomputed scalars (:func:`jepsen_tpu.lin.bfs.
_fused_row_tables` — the register family's mutator step is a value
match, so ok/post per (column, state) collapse to per-column
scalars), and each pass's dedup is the psort bitonic sort pair.

SCOPE (round-5 lore, ISSUE 14): the fused kernel serves the
NON-dominance dedups only — the crash-dom band's dominance dedups
keep the FORCED-LAX chain rule (both round-5 runs that routed them
through pallas kernels faulted the chip; see psort
_assert_force_window_interpret_only). Call sites therefore gate on
``crash_dom=False``, and the engine integration lives in
``bfs._search_chunk_keys`` (the healthy compact band's row tiers).

Semantics twin of the unfused chain: one fused fixpoint ==
``_closure_pass_keys_compact`` iterated to convergence (ungrouped,
non-dominance dedup), parity-fuzzed in interpret mode in
``tests/test_lin_psort_fused.py`` — the psort precedent. Every loop
carries its iteration ceiling (``it_max`` — the round-5 invariant,
``make lint`` while-ceiling rule); a ceiling hit with changes pending
reports non-convergence, which the engine maps to an honest overflow.

Layout: the working array is the full candidate space
``[(1+M)*cap]`` (padded to a power of two), viewed ``[SP, 128]``:
block 0 holds the carried (compacted) frontier, block k the
expansions by mutator column k. Each block's base values are the
carried prefix ROLLED down by ``k*cap/128`` sublanes — a native VPU
movement, no gather, no concat (Mosaic legalization lore). ``cap``
must be a LANE multiple power of two (every engine cap is; odd test
caps fall back to the unfused chain).

Env: ``JEPSEN_TPU_PSORT_FUSED`` (doc/env.md) — ``0`` forces the
unfused chain; platform/interpret gating follows ``psort.backend_ok``.
``JEPSEN_TPU_PSORT_FUSED_MAX_N`` (an exponent) raises the candidate-
space bound past the default ``psort.PSORT_MAX_N`` so the PAIR-KEY
in-chunk tiers at the big caps engage the kernel too — see
:func:`max_n`. The raise is env-gated OFF by default and the bench
engages it only behind its small-input smoke probe (fault lore:
rows*cap program complexity is the fault driver; never spend a
multi-hour rung on an unprobed shape).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jepsen_tpu.lin import psort
from jepsen_tpu.lin.psort import (KEY_FILL, LANE, _bitonic_sort,
                                  _bitonic_sort2, _flat_prev)

# Column-scalar table rows (bfs._fused_row_tables builds them; the
# kernel reads them as SMEM scalars per static column index).
COL_EXP_LO, COL_EXP_HI = 0, 1
COL_PRED_LO, COL_PRED_HI = 2, 3
COL_RV_LO, COL_RV_HI = 4, 5
COL_OR_LO, COL_OR_HI = 6, 7
COL_PRE, COL_FLAGS = 8, 9
N_COL_ROWS = 10
FLAG_ACT, FLAG_WRITE, FLAG_JIT = 1, 2, 4


def enabled() -> bool:
    """Fused-fixpoint gate: ``JEPSEN_TPU_PSORT_FUSED=0`` forces the
    unfused pass chain (fault triage / A-B timing), ``interpret``
    forces the kernel in interpreter mode (CPU parity tests — its own
    knob, so parity runs even where the psort kernels are gated off);
    otherwise the kernel engages on the real TPU backend wherever
    :func:`fits` holds — the psort gating convention."""
    mode = os.environ.get("JEPSEN_TPU_PSORT_FUSED", "1")
    if mode == "0":
        return False
    return mode == "interpret" or psort._on_tpu()


def _interpret() -> bool:
    """Interpreter-mode gate for the pallas_call itself — keyed off
    THIS module's knob (plus the platform), so
    ``JEPSEN_TPU_PSORT_FUSED=interpret`` on a real TPU actually runs
    the interpreter (the documented triage path), independent of
    ``JEPSEN_TPU_PSORT``."""
    return os.environ.get("JEPSEN_TPU_PSORT_FUSED") == "interpret" \
        or not psort._on_tpu()


# Hard ceiling for the env-raised candidate-space bound: 2^21 is the
# largest in-VMEM bitonic sort PROVEN on this chip (psort module
# docstring — the raised scoped-VMEM limit sorts to 2^21). Beyond it
# nothing has run; the knob clamps rather than trusts.
FUSED_MAX_EXP = 21
# Pair keys carry twice the VMEM working set: compiled for a v5e chip,
# the pair kernel at a 2^19 candidate space needs 155 MiB of its 128 MiB
# VMEM, and at 2^18 it fits. The bound holds whatever max_n() says.
PAIR_MAX_N = 1 << 18


def max_n() -> int:
    """The fused kernel's candidate-space bound (padded elements).
    Default ``psort.PSORT_MAX_N`` — the proven envelope every rung
    runs inside. ``JEPSEN_TPU_PSORT_FUSED_MAX_N`` (an EXPONENT, the
    DOM_WINDOW convention) raises it so the pair-key in-chunk tiers at
    the big caps engage the kernel, clamped to ``2^FUSED_MAX_EXP``
    (the proven sort bound) — the bench's partitioned ladder sets it
    on its ``fusedtier`` rung only after the small-input smoke leg ran
    the raised shape clean on the chip. Read OUTSIDE jit and passed as
    a static argument (``bfs`` plumbs it through ``use_fused``), so a
    mid-process env change can never hit a stale traced gate."""
    env = os.environ.get("JEPSEN_TPU_PSORT_FUSED_MAX_N", "")
    if not env:
        return psort.PSORT_MAX_N
    return 1 << min(FUSED_MAX_EXP, max(10, int(env)))


def fits(cap: int, M: int, b: int, max_pad: int | None = None,
         pair: bool = False) -> bool:
    """Size/shape gate: the candidate space must fit the in-VMEM sort
    bound (``max_pad`` — callers inside jit pass the env-resolved
    :func:`max_n` value; None keeps the proven default; pair keys are
    further held to :data:`PAIR_MAX_N`), the block
    roll trick needs cap to be a LANE-multiple power of two, and the
    per-column scalar encoding needs the packed state id to fit 6 bits
    (the compact band's own bound)."""
    bound = max_pad if max_pad else psort.PSORT_MAX_N
    if pair:
        bound = min(bound, PAIR_MAX_N)
    return (b <= 6 and cap >= LANE and (cap & (cap - 1)) == 0
            and psort.pad_size(cap * (1 + M)) <= bound)


def _sat_select(sv, live, sat_ref, plane: int, nb: int):
    """2^b-way unrolled select of the saturation mask for each
    config's state id (the in-kernel twin of the engine's sat-table
    branch; bounded by b <= 6)."""
    sat = jnp.zeros_like(sv)
    for s in range(1 << nb):
        sat = sat | jnp.where(live & (sv == jnp.uint32(s)),
                              sat_ref[plane, s], jnp.uint32(0))
    return sat


def _fixpoint_body(scal_ref, cols_ref, sat_ref, *refs, SP, S0, M, K,
                   b, cap, it_max, pair):
    """One whole closure fixpoint in VMEM (module docstring). refs:
    (lo_ref[, hi_ref], out_lo_ref[, out_hi_ref], flags_ref)."""
    if pair:
        lo_ref, hi_ref, out_lo_ref, out_hi_ref, flags_ref = refs
    else:
        lo_ref, out_lo_ref, flags_ref = refs
        hi_ref = out_hi_ref = None
    fill = jnp.uint32(KEY_FILL)
    smask = jnp.uint32((1 << b) - 1)
    logcap = cap.bit_length() - 1
    x0 = lo_ref[:]
    xh0 = hi_ref[:] if pair else x0
    lane = lax.broadcasted_iota(jnp.uint32, x0.shape, 1)
    row = lax.broadcasted_iota(jnp.uint32, x0.shape, 0)
    flat = row * LANE + lane
    blk = flat >> logcap
    blk0 = blk == 0

    def one_pass(x, xh, cnt):
        # Liveness: live keys never collide with KEY_FILL (single key:
        # window+b <= 31 keeps bit 31 clear; pair: the hi payload is
        # <= 28 bits) and dead entries are FILL by compaction.
        live = (xh != fill) if pair else (x != fill)
        sv = x & smask
        # Carried saturation in place (engine: lo1 = lo_in | sat).
        sat_lo = _sat_select(sv, live, sat_ref, 0, b)
        x1 = jnp.where(live, x | sat_lo, x)
        if pair:
            sat_hi = _sat_select(sv, live, sat_ref, 1, b)
            xh1 = jnp.where(live, xh | sat_hi, xh)
        else:
            xh1 = x1
        # Candidates: block 0 = carried; block k = expansion by
        # mutator column k-1, its base values the carried prefix
        # rolled into place (sublane roll — no gather/concat).
        cand = jnp.where(blk0, x1, fill)
        candh = jnp.where(blk0, xh1, fill) if pair else cand
        for kb in range(1, M + 1):
            base = pltpu.roll(x1, kb * S0, 0)
            baseh = pltpu.roll(xh1, kb * S0, 0) if pair else base
            c = kb - 1
            flg = cols_ref[COL_FLAGS, c]
            exp_lo = cols_ref[COL_EXP_LO, c]
            pred_lo = cols_ref[COL_PRED_LO, c]
            rv_lo = cols_ref[COL_RV_LO, c]
            or_lo = cols_ref[COL_OR_LO, c]
            pre = cols_ref[COL_PRE, c]
            blive = (baseh != fill) if pair else (base != fill)
            bsv = base & smask
            okc = ((flg & FLAG_WRITE) != 0) | (bsv == pre)
            already = (base & exp_lo) != 0
            chain = (base & pred_lo) == pred_lo
            jit_ok = ((flg & FLAG_JIT) != 0) | ((rv_lo & ~base) != 0)
            if pair:
                exp_hi = cols_ref[COL_EXP_HI, c]
                pred_hi = cols_ref[COL_PRED_HI, c]
                rv_hi = cols_ref[COL_RV_HI, c]
                or_hi = cols_ref[COL_OR_HI, c]
                already = already | ((baseh & exp_hi) != 0)
                chain = chain & ((baseh & pred_hi) == pred_hi)
                jit_ok = jit_ok | ((rv_hi & ~baseh) != 0)
            legal = blive & ((flg & FLAG_ACT) != 0) & okc \
                & ~already & chain & jit_ok
            newl = (base & ~smask) | or_lo
            sel = blk == jnp.uint32(kb)
            cand = jnp.where(sel, jnp.where(legal, newl, fill), cand)
            if pair:
                newh = baseh | or_hi
                candh = jnp.where(sel, jnp.where(legal, newh, fill),
                                  candh)
        # Sort + adjacent-dup drop + compaction re-sort (the psort
        # dedup semantics; FILL doubles as the invalid flag — bit 31).
        first = flat == 0
        if pair:
            sh, sl = _bitonic_sort2(candh, cand, flat, S=SP, K=K)
            dup = (sh == _flat_prev(sh, 1, SP)) \
                & (sl == _flat_prev(sl, 1, SP))
            keep = (sh >> 31 == 0) & (first | ~dup)
            total = jnp.sum(keep.astype(jnp.int32))
            sh = jnp.where(keep, sh, fill)
            sl = jnp.where(keep, sl, fill)
            sh, sl = _bitonic_sort2(sh, sl, flat, S=SP, K=K)
            changed = jnp.sum((((sl != x) | (sh != xh)) & blk0)
                              .astype(jnp.int32)) > 0
        else:
            s1 = _bitonic_sort(cand, flat, lane, S=SP, K=K)
            dup = s1 == _flat_prev(s1, 1, SP)
            keep = (s1 >> 31 == 0) & (first | ~dup)
            total = jnp.sum(keep.astype(jnp.int32))
            sl = _bitonic_sort(jnp.where(keep, s1, fill), flat, lane,
                               S=SP, K=K)
            sh = sl
            changed = jnp.sum(((sl != x) & blk0)
                              .astype(jnp.int32)) > 0
        changed = changed | (total != cnt)
        return sl, sh, total, changed, total > cap

    def cond(c):
        _, _, _, it, changed, ovf = c
        return changed & ~ovf & (it < it_max)

    def body(c):
        x, xh, cnt, it, _, ovf = c
        x2, xh2, n2, changed, o2 = one_pass(x, xh, cnt)
        return x2, xh2, n2, it + 1, changed, ovf | o2

    x, xh, cnt, it, changed, ovf = lax.while_loop(
        cond, body,
        (x0, xh0, scal_ref[0], jnp.int32(0), jnp.bool_(True),
         jnp.bool_(False)))
    out_lo_ref[:] = x
    if pair:
        out_hi_ref[:] = xh
    flags_ref[0] = (~changed & ~ovf).astype(jnp.int32)
    flags_ref[1] = ovf.astype(jnp.int32)
    flags_ref[2] = it
    flags_ref[3] = cnt


@partial(jax.jit, static_argnames=("cap", "b", "it_max", "pair", "M"))
def _fixpoint_call(lo, hi, count, cols, sats, *, cap, b, it_max, pair,
                   M):
    n_pad = psort.pad_size(cap * (1 + M))
    SP = n_pad // LANE
    S0 = cap // LANE
    K = n_pad.bit_length() - 1
    pad = jnp.full(n_pad - cap, KEY_FILL, jnp.uint32)
    ins = [jnp.stack([count]).astype(jnp.int32),
           cols.astype(jnp.uint32), sats.astype(jnp.uint32),
           jnp.concatenate([lo, pad]).reshape(SP, LANE)]
    out_shape = [jax.ShapeDtypeStruct((SP, LANE), jnp.uint32)]
    aliases = {3: 0}
    if pair:
        ins.append(jnp.concatenate([hi, pad]).reshape(SP, LANE))
        out_shape.append(jax.ShapeDtypeStruct((SP, LANE), jnp.uint32))
        aliases[4] = 1
    out_shape.append(jax.ShapeDtypeStruct((4,), jnp.int32))
    outs = pl.pallas_call(
        partial(_fixpoint_body, SP=SP, S0=S0, M=M, K=K, b=b, cap=cap,
                it_max=it_max, pair=pair),
        name="psort_fused_fixpoint",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * (2 if pair else 1),
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)]
        * (2 if pair else 1)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(*ins)
    if pair:
        out_lo, out_hi, flags = outs
        return (out_lo.reshape(-1)[:cap], out_hi.reshape(-1)[:cap],
                flags)
    out_lo, flags = outs
    return out_lo.reshape(-1)[:cap], None, flags


def fixpoint(lo, hi, count, cols, sats, *, cap, b, it_max):
    """Run one whole closure fixpoint in VMEM. ``lo``/``hi`` are the
    carried key arrays (``[cap]``, KEY_FILL-compacted; ``hi`` None for
    single-word keys), ``cols``/``sats`` the per-row scalar tables
    from ``bfs._fused_row_tables``. Caller must have checked
    :func:`fits`. Returns (lo[cap], hi[cap]|None, count, converged,
    overflow) — non-convergence at the ``it_max`` ceiling is the
    engine's honest-budget-overflow signal, dedup overflow its
    capacity-escalation signal, exactly like the unfused chain."""
    pair = hi is not None
    M = int(cols.shape[1])
    lo2, hi2, flags = _fixpoint_call(lo, hi, count, cols, sats,
                                     cap=cap, b=b, it_max=it_max,
                                     pair=pair, M=M)
    return lo2, hi2, flags[3], flags[0] != 0, flags[1] != 0
