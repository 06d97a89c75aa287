"""Pallas TPU kernel for the dense bitmap engine's chunk loop.

The XLA formulation (:mod:`jepsen_tpu.lin.dense`) paid a fixed ~100us
per return event in loop overhead in round 4 — flat even on a 64-word
bitmap — because every row is dozens of small HLOs round-tripping HBM. This kernel keeps the ENTIRE frontier bitmap resident in VMEM
scratch across a sequential grid (one program per return event), so a
row costs exactly its vector math:

- Bitmap layout ``u32[2**w / 128, 128]``: a config's bitset B splits as
  (sublane row = B >> 7, lane = B & 127). Linearizing slot j < 7 is a
  LANE roll by 2**j; slot j >= 7 a SUBLANE roll by 2**(j-7) — both
  native VPU data movements, with the source masked to bit-j-clear
  positions so nothing wraps into garbage.
- The model-step tables are compressed into *transition masks*:
  ``mask[r, j, s'] = bitmask of source states s that op (r,j) maps to
  s'`` (inactive slots are all-zero). One u32 per (slot, target-state),
  so the whole per-row table is a [w, ns] block streamed into SMEM by
  the grid pipeline, and the closure's inner loop is
  ``contrib |= ((src & mask) != 0) << s'`` — scalar SMEM reads driving
  pure vector ops, no gathers.
- The closure do-while and the return filter (one dynamic lane or
  sublane roll by the returner's slot) run inside the kernel; a dead
  frontier flips an SMEM flag that short-circuits every later grid step.

The host-side chunk loop, snapshots, witness replay, and routing all
stay in :mod:`jepsen_tpu.lin.dense` — this module only provides the
drop-in chunk function (``check_packed(..., backend="pallas")``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane bits: the low 7 bitset bits live on the 128-lane axis.
LANE_BITS = 7
# Sublane tiling for 32-bit types is 8 rows: minimum bitmap 8*128 words.
MIN_W = LANE_BITS + 3
MAX_PALLAS_W = 18          # 2**18 words = 1 MiB bitmap in VMEM
# The kernel's explicit name: the compiled custom call and the device
# op in a profile carry it (doc/observability.md, "Kernel names").
KERNEL_NAME = "dense_pallas_chunk"


@partial(jax.jit, static_argnames=("ns", "step_fn"))
def transition_masks(slot_f, slot_v, active, nil_id, *, ns, step_fn):
    """u32[CH, w, ns] transition masks: bit s of mask[r, j, s'] set iff
    op (r, j) is active, legal in state s, and maps s to s'. Built on
    the same shared tables as the XLA backend (dense.transition_tables),
    just pivoted into per-target-state source bitmasks."""
    from jepsen_tpu.lin.dense import transition_tables

    ok, to = transition_tables(slot_f, slot_v, active, nil_id,
                               ns=ns, step_fn=step_fn)
    sid = jnp.arange(ns, dtype=jnp.uint32)
    # mask[r,j,s'] = OR over source s of (ok & to==s') << s
    hit = ok[..., None] & (to[..., None] == sid[None, None, None, :])
    bit = (jnp.uint32(1) << sid)[None, None, :, None]
    return jnp.sum(jnp.where(hit, bit, jnp.uint32(0)), axis=2,
                   dtype=jnp.uint32)


def _row_kernel(n_rows_ref, masks_ref, ret_ref, f_in_ref, f_out_ref,
                done_ref, f_ref, state_ref, *, w, ns):
    """One grid step = one return event. f_ref: VMEM scratch [S,128]
    persisting across steps; state_ref: SMEM [2] = (dead, rows_done)."""
    r = pl.program_id(0)
    S = 1 << (w - LANE_BITS)

    @pl.when(r == 0)
    def _init():
        f_ref[:] = f_in_ref[:]
        state_ref[0] = 0
        state_ref[1] = 0

    lane = lax.broadcasted_iota(jnp.uint32, (S, 128), 1)
    row = lax.broadcasted_iota(jnp.uint32, (S, 128), 0)

    def bit_clear(j):
        if j < LANE_BITS:
            return (lane & (1 << j)) == 0
        return (row & (1 << (j - LANE_BITS))) == 0

    def shift_up(x, j):        # B -> B + 2**j (sources pre-masked)
        if j < LANE_BITS:
            return pltpu.roll(x, 1 << j, 1)
        return pltpu.roll(x, 1 << (j - LANE_BITS), 0)

    @pl.when((r < n_rows_ref[0]) & (state_ref[0] == 0))
    def _step():
        F = f_ref[:]

        def closure_body(c):
            F, _ = c
            F2 = F
            for j in range(w):
                src = jnp.where(bit_clear(j), F2, jnp.uint32(0))
                contrib = jnp.zeros_like(src)
                for sp in range(ns):
                    m = masks_ref[0, j, sp]
                    contrib = contrib | jnp.where(
                        (src & m) != 0, jnp.uint32(1 << sp),
                        jnp.uint32(0))
                F2 = F2 | shift_up(contrib, j)
            return F2, jnp.any(F2 != F)

        # lint: unbounded-ok — monotone OR-accumulated bitmap closure
        # (dense.py's termination argument: <= w+1 passes); a carried
        # counter here would cost Mosaic an extra SMEM carry for a
        # bound that provably never binds.
        F, _ = lax.while_loop(lambda c: c[1], closure_body,
                              closure_body((F, True)))

        # Return filter: keep configs with the returner's bit, clear it
        # (B -> B - 2**s). One dynamic roll on the lane or sublane axis:
        # a per-slot lax.switch nests one region per slot, and Mosaic's
        # layout inference overflowed its stack on it from w = 12 up.
        s = ret_ref[0, 0, 0]
        bit = ((row << LANE_BITS) | lane) >> s.astype(jnp.uint32)
        keep = jnp.where((bit & 1) == 1, F, jnp.uint32(0))
        F = lax.cond(
            s < LANE_BITS,
            lambda x: pltpu.roll(x, 128 - (1 << s), 1),
            lambda x: pltpu.roll(x, S - (1 << (s - LANE_BITS)), 0),
            keep)
        f_ref[:] = F
        dead = jnp.all(F == 0)
        state_ref[0] = jnp.where(dead, 1, 0).astype(jnp.int32)
        state_ref[1] = r + 1

    @pl.when(r == pl.num_programs(0) - 1)
    def _finish():
        f_out_ref[:] = f_ref[:]
        done_ref[0] = state_ref[0]
        done_ref[1] = state_ref[1]


@partial(jax.jit, static_argnames=("w", "ns", "chunk", "interpret"))
def pallas_chunk(F, n_rows, masks, ret_slot, *, w, ns, chunk,
                 interpret=False):
    """Advance the frontier through up to n_rows return events.
    F: u32[2**w] (1D, the dense engine's carry format); masks:
    u32[chunk, w, ns]; ret_slot: i32[chunk].
    Returns (F, rows_done, dead) matching dense._dense_chunk."""
    S = 1 << (w - LANE_BITS)
    F2d = F.reshape(S, 128)
    grid = (chunk,)
    f_out, done = pl.pallas_call(
        partial(_row_kernel, w=w, ns=ns),
        name=KERNEL_NAME,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # n_rows
            pl.BlockSpec((1, w, ns), lambda r: (r, 0, 0),
                         memory_space=pltpu.SMEM),             # masks row
            pl.BlockSpec((1, 1, 1), lambda r: (r, 0, 0),
                         memory_space=pltpu.SMEM),             # ret slot
            pl.BlockSpec(memory_space=pltpu.VMEM),             # F in
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 128), jnp.uint32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, 128), jnp.uint32),
            pltpu.SMEM((2,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(n_rows.reshape(1), masks, ret_slot.reshape(-1, 1, 1), F2d)
    return f_out.reshape(-1), done[1], done[0] != 0


def supported_w(w: int) -> int | None:
    """The pallas bitmap width for a dense-plan width, or None when the
    kernel can't take it. Widths below the tiling minimum are padded up
    (extra slots are never active, so the cost is only bitmap size)."""
    if w > MAX_PALLAS_W:
        return None
    return max(w, MIN_W)
