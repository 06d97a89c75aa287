"""Counterexample reconstruction for device-decided violations.

The dense engine (:mod:`jepsen_tpu.lin.dense`) decides validity with a
frontier bitmap that carries no parent pointers — storing paths on device
would burn HBM bandwidth on the 99% case (valid histories) to serve the
1% (violations). Instead the device search retains its per-chunk entry
bitmaps (a few KB each), and on an invalid verdict this module replays
JUST the failing tail on the host:

1. take the last snapshot at or before the dead row — the *exact* closed
   config set the device search had there (the bitmap is the
   characteristic function, so no information is lost);
2. run the CPU oracle's closure (:func:`jepsen_tpu.lin.cpu.search_rows`)
   from that set through the dead row, tracking linearization order via
   shared-structure cons cells;
3. emit knossos-style ``final-paths`` — for each config alive at the
   failure, its model state and the op path that reached it — the shape
   the reference renders at checker.clj:96-107.

The replay is bounded by one chunk of return events regardless of history
length, so a 100k-op violation costs a <=CHUNK-row host replay, not a
full re-check.
"""

from __future__ import annotations

from jepsen_tpu.lin import cpu, dense
from jepsen_tpu.lin.prepare import PackedHistory
from jepsen_tpu.obs import trace as obs_trace


def replay_configs(p: PackedHistory, configs: set, base: int,
                   dead_row: int, cancel=None) -> dict:
    """Run the CPU oracle's closure from a known config set at row
    ``base`` through ``dead_row``, tracking linearization order, and
    emit knossos-style configs + final-paths at the death. Returns {}
    on failure/cancel (reporting is best-effort, like the reference's
    render at checker.clj:96-103)."""
    if not configs:
        return {}
    obs_trace.count("witness.configs", len(configs))
    obs_trace.count("witness.rows", dead_row - base + 1)
    order = {cfg: None for cfg in configs}
    with obs_trace.span("witness.replay"):
        try:
            cpu.search_rows(p, configs, order, base, dead_row + 1,
                            cancel=cancel)
        except cpu.Dead as d:
            return {"configs": cpu._decode_configs(p, d.seen, d.r),
                    "final-paths": cpu._final_paths(p, d.seen, d.order)}
        except Exception:
            return {}
    # The tail replay survived where the device died: a disagreement
    # between engines — surface it rather than fabricate a path.
    return {"error": "tail replay disagrees with device verdict "
                     f"(rows {base}..{dead_row} survive on host)"}


def tail_replay(p: PackedHistory, nil_id: int, snapshots: list,
                dead_row: int, cancel=None) -> dict:
    """Dense-engine counterexample: decode the last chunk-entry bitmap
    at or before ``dead_row`` and replay the failing tail.
    ``cancel`` keeps a competition loser's replay from blocking the
    race join."""
    with obs_trace.span("witness"):
        usable = [(b, F) for b, F in snapshots if b <= dead_row]
        if not usable:
            return {}
        base, F = usable[-1]
        with obs_trace.span("witness.decode"):
            configs = {(bits, st)
                       for bits, st in dense.decode_bitmap(F, nil_id)}
        return replay_configs(p, configs, base, dead_row, cancel=cancel)


def tail_replay_sparse(p: PackedHistory, snapshots: list,
                       dead_row: int, cancel=None) -> dict:
    """Sparse-engine counterexample: snapshots are
    ``(base_row, bits[cap,NW], state[cap,S], count)`` chunk-entry
    frontiers; decode the multi-word bitsets and replay the tail."""
    import numpy as np

    with obs_trace.span("witness"):
        usable = [s for s in snapshots if s[0] <= dead_row]
        if not usable:
            return {}
        base, bits, state, count = usable[-1]
        with obs_trace.span("witness.decode"):
            n = min(int(count), np.asarray(bits).shape[0])
            bits = np.asarray(bits)[:n].astype(object)
            state = np.asarray(state)[:n]
            packed = bits[:, 0]
            for w in range(1, bits.shape[1]):
                packed = packed | (bits[:, w] << (32 * w))
            configs = set(zip((int(b) for b in packed),
                              map(tuple, state.tolist())))
        return replay_configs(p, configs, base, dead_row, cancel=cancel)
