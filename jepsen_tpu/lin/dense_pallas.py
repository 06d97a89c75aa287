"""Pallas TPU kernel for the dense bitmap engine's chunk loop.

The XLA formulation (:mod:`jepsen_tpu.lin.dense`) paid a fixed ~100us
per return event in loop overhead in round 4 — flat even on a 64-word
bitmap — because every row is dozens of small HLOs round-tripping HBM. This kernel keeps the ENTIRE frontier bitmap resident in VMEM
scratch across a sequential grid, so a row costs its vector math:

- Bitmap layout ``u32[2**w / 128, 128]``: a config's bitset B splits as
  (sublane row = B >> 7, lane = B & 127). Linearizing slot j < 7 is a
  LANE roll by 2**j; slot j >= 7 a SUBLANE roll by 2**(j-7) — both
  native VPU data movements, with the source masked to bit-j-clear
  positions so nothing wraps into garbage.
- The model-step tables are compressed into *transition masks*:
  ``mask[j, s', r] = bitmask of source states s that op (r,j) maps to
  s'`` (inactive slots are all-zero). One u32 per (slot, target-state),
  so the closure's inner loop is ``contrib |= ((src & mask) != 0) <<
  s'`` — scalar SMEM reads driving pure vector ops, no gathers.
- The block feed: each grid step sweeps a block of
  :func:`block_rows` return rows. The pipeline DMAs the block's masks
  ``(w, ns, rows)`` and return slots ``(1, 1, rows)`` into SMEM, rows
  on the 128-lane axis so Mosaic pads neither, and fetches the next
  block while this one runs. (Mosaic lays a per-row ``(1, w, ns)``
  SMEM block out as ``1 x 16 x 128`` words: 8 KiB for ~100 useful.)
- The closure do-while (Jacobi or Gauss-Seidel passes by bitmap size:
  ``JACOBI_MAX_S``) and the return filter (one dynamic lane or sublane
  roll by the returner's slot) run inside the kernel for each row of
  the block. The block's end finds the first row whose frontier
  emptied and flips an SMEM flag that makes every later block a
  no-op.

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
# Rows per grid step: the lane width of the masks block's last axis.
# A block holds at most MAX_PALLAS_W * 32 states * 128 rows = 73,728
# words (288 KiB), double-buffered in the v5e's 1 MiB SMEM;
# tests/test_chip_compile.py compiles a larger one.
ROW_BLOCK = 128
# Closure pass order, by bitmap size. Linearizing a slot ends in a
# roll, and a lane roll's latency on the XLU is long. A Gauss-Seidel
# pass (slot j reads the frontier slots < j left) chains w of them; a
# Jacobi pass (every slot reads the pass's input) issues them back to
# back, for ~0.5 more passes a row (both reach the same fixpoint). Up
# to JACOBI_MAX_S sublane rows (4 vregs, w = 12) the bitmap is too
# small to hide the chain: Jacobi; above, a pass is bound by its vector
# work and the fewer passes win: Gauss-Seidel (PERF.md § 5).
JACOBI_MAX_S = 32
# The kernel's explicit name: the compiled custom call and the device
# op in a profile carry it (doc/observability.md, "Kernel names").
KERNEL_NAME = "dense_pallas_chunk"


def block_rows(chunk: int) -> int:
    """Return rows a grid step sweeps: ROW_BLOCK, or all of a shorter
    chunk."""
    return min(ROW_BLOCK, chunk)


@partial(jax.jit, static_argnames=("ns", "step_fn"))
def transition_masks(slot_f, slot_v, active, nil_id, *, ns, step_fn):
    """u32[w, ns, CH] transition masks: bit s of mask[j, s', r] set iff
    op (r, j) is active, legal in state s, and maps s to s'. Built on
    the same shared tables as the XLA backend (dense.transition_tables),
    just pivoted into per-target-state source bitmasks. Rows run along
    the last axis, the layout XLA gives the tables, so the kernel's
    blocks of rows need no relayout."""
    from jepsen_tpu.lin.dense import transition_tables

    ok, to = transition_tables(slot_f, slot_v, active, nil_id,
                               ns=ns, step_fn=step_fn)
    sid = jnp.arange(ns, dtype=jnp.uint32)
    # mask[r,j,s'] = OR over source s of (ok & to==s') << s
    hit = ok[..., None] & (to[..., None] == sid[None, None, None, :])
    bit = (jnp.uint32(1) << sid)[None, None, :, None]
    return jnp.sum(jnp.where(hit, bit, jnp.uint32(0)), axis=2,
                   dtype=jnp.uint32).transpose(1, 2, 0)


def _block_kernel(n_rows_ref, masks_ref, ret_ref, f_in_ref, f_out_ref,
                  done_ref, f_ref, live_ref, state_ref, *, w, ns, rb):
    """One grid step = one block of rb return events, swept in order.
    masks_ref: SMEM u32[w, ns, rb], row k's table at [:, :, k];
    ret_ref: SMEM i32[1, 1, rb]. f_ref: VMEM scratch [S,128] persisting
    across steps; state_ref: SMEM [2] = (dead, rows_done).

    Rows past n_rows do nothing. An empty frontier stays empty, so the
    dead row is found once a block, off the rows' critical path: row k
    leaves its frontier's per-lane liveness in live_ref[k], and the
    block's end names the first empty row. Rows after it in its block
    run on the empty frontier; the blocks after it do nothing."""
    b = pl.program_id(0)
    S = 1 << (w - LANE_BITS)
    jacobi = S <= JACOBI_MAX_S

    @pl.when(b == 0)
    def _init():
        f_ref[:] = f_in_ref[:]
        state_ref[0] = 0
        state_ref[1] = 0

    live_ref[:] = jnp.ones((rb, 128), jnp.int32)

    def row_body(k, carry):
        r = b * rb + k

        @pl.when(r < n_rows_ref[0])
        def _row():
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

            F = f_ref[:]

            def closure_body(c):
                F, _ = c
                F2 = F
                for j in range(w):
                    src = jnp.where(bit_clear(j), F if jacobi else F2,
                                    jnp.uint32(0))
                    contrib = jnp.zeros_like(src)
                    for sp in range(ns):
                        m = masks_ref[j, sp, k]
                        contrib = contrib | jnp.where(
                            (src & m) != 0, jnp.uint32(1 << sp),
                            jnp.uint32(0))
                    F2 = F2 | shift_up(contrib, j)
                return F2, jnp.any(F2 != F)

            # lint: unbounded-ok — monotone OR-accumulated bitmap closure
            # (dense.py's termination argument: <= w+1 passes in either
            # order, pass k reaching every chain of k linearizations); a
            # carried counter here would cost Mosaic an extra SMEM carry
            # for a bound that provably never binds.
            F, _ = lax.while_loop(lambda c: c[1], closure_body,
                                  closure_body((F, True)))

            # Return filter: keep configs with the returner's bit, clear
            # it (B -> B - 2**s). One dynamic roll on the lane or sublane
            # axis: a per-slot lax.switch nests one region per slot, and
            # Mosaic's layout inference overflowed its stack on it from
            # w = 12 up.
            s = ret_ref[0, 0, k]
            bit = ((row << LANE_BITS) | lane) >> s.astype(jnp.uint32)
            keep = jnp.where((bit & 1) == 1, F, jnp.uint32(0))
            F = lax.cond(
                s < LANE_BITS,
                lambda x: pltpu.roll(x, 128 - (1 << s), 1),
                lambda x: pltpu.roll(x, S - (1 << (s - LANE_BITS)), 0),
                keep)
            f_ref[:] = F
            live_ref[pl.ds(k, 1), :] = jnp.max(
                (F != 0).astype(jnp.int32), axis=0, keepdims=True)
            state_ref[1] = r + 1

        return carry

    @pl.when(state_ref[0] == 0)
    def _sweep():
        lax.fori_loop(0, rb, row_body, 0)
        live = jnp.max(live_ref[:], axis=1, keepdims=True)
        first = jnp.min(jnp.where(
            live == 0, lax.broadcasted_iota(jnp.int32, (rb, 1), 0), rb))

        @pl.when(first < rb)
        def _dead():
            state_ref[0] = 1
            state_ref[1] = b * rb + first + 1

    @pl.when(b == pl.num_programs(0) - 1)
    def _finish():
        f_out_ref[:] = f_ref[:]
        done_ref[0] = state_ref[0]
        done_ref[1] = state_ref[1]


@partial(jax.jit, static_argnames=("w", "ns", "chunk", "interpret"))
def pallas_chunk(F, n_rows, masks, ret_slot, *, w, ns, chunk,
                 interpret=False):
    """Advance the frontier through up to n_rows return events.
    F: u32[2**w] (1D, the dense engine's carry format); masks:
    u32[w, ns, chunk] (:func:`transition_masks`); ret_slot:
    i32[chunk]. Returns (F, rows_done, dead) matching
    dense._dense_chunk."""
    S = 1 << (w - LANE_BITS)
    rb = block_rows(chunk)
    rows = -(-chunk // rb) * rb
    if rows > chunk:          # rows past n_rows are never swept
        masks = jnp.pad(masks, ((0, 0), (0, 0), (0, rows - chunk)))
        ret_slot = jnp.pad(ret_slot, (0, rows - chunk))
    F2d = F.reshape(S, 128)
    f_out, done = pl.pallas_call(
        partial(_block_kernel, w=w, ns=ns, rb=rb),
        name=KERNEL_NAME,
        grid=(rows // rb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # n_rows
            pl.BlockSpec((w, ns, rb), lambda b: (0, 0, b),
                         memory_space=pltpu.SMEM),             # masks
            pl.BlockSpec((1, 1, rb), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),             # ret slots
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
            pltpu.VMEM((rb, 128), jnp.int32),
            pltpu.SMEM((2,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(n_rows.reshape(1), masks,
      ret_slot.reshape(rows // rb, 1, rb), F2d)
    return f_out.reshape(-1), done[1], done[0] != 0


def supported_w(w: int) -> int | None:
    """The pallas bitmap width for a dense-plan width, or None when the
    kernel can't take it. Widths below the tiling minimum are padded up
    (extra slots are never active, so the cost is only bitmap size)."""
    if w > MAX_PALLAS_W:
        return None
    return max(w, MIN_W)
