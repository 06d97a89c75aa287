"""Linearizability checking — the compute kernel of the framework.

This package replaces the external knossos solver the reference delegates to
(`jepsen/src/jepsen/checker.clj:82-107`, knossos 0.3.1 per
`jepsen/project.clj:9`). The search is reframed TPU-first: instead of
knossos's JVM graph search (`knossos.linear` / `knossos.wgl`), linearizability
is decided by a breadth-first frontier over
``(linearized-op-bitset x model-state)`` configurations:

- :mod:`jepsen_tpu.lin.prepare` — host-side packing: invoke/completion
  pairing, concurrency-window slot assignment, value interning, the
  return-event table both backends consume.
- :mod:`jepsen_tpu.lin.cpu`     — host reference implementation of the
  just-in-time linearization closure (semantic spec + fallback for models
  without device kernels; analogue of knossos.linear).
- :mod:`jepsen_tpu.lin.bfs`     — the sparse device kernel: frontier in
  HBM as packed u32 keys (single word to window 31-b, (hi, lo) pairs to
  60), mutator-compacted expansion, per-row count tiers, canonical
  chains + dominance pruning over crashed/read bits.
- :mod:`jepsen_tpu.lin.dense`   — the dense config-space bitmap engine
  (windows <= 20): the frontier as its characteristic function.
- :mod:`jepsen_tpu.lin.psort`   — in-VMEM pallas bitonic sort-dedup
  kernels backing the sparse engine's per-pass dedup.
- :mod:`jepsen_tpu.lin.sharded` — pjit/shard_map multi-chip frontier with
  collective dedup over ICI.
- :mod:`jepsen_tpu.lin.brute`   — tiny exhaustive search used to test the
  testers.

``analysis(model, history)`` mirrors the shape of
``knossos.competition/analysis`` results consumed at checker.clj:104-107:
``{"valid?": bool, "op": ..., "configs": [...], "final-paths": [...]}``,
plus the request's record: ``spans`` and ``counts``
(:func:`jepsen_tpu.obs.trace.request`, doc/observability.md).
"""

from __future__ import annotations

import contextvars
import threading
from typing import Any

from jepsen_tpu.lin import prepare as _prepare_mod
from jepsen_tpu.lin.prepare import PackedHistory, UnsupportedHistory
from jepsen_tpu.obs import trace as _trace


def analysis(model, history, algorithm: str = "competition", **kw) -> dict:
    """Decide linearizability of ``history`` against ``model``.

    algorithm: ``"tpu"`` (device BFS), ``"cpu"`` (host reference), or
    ``"competition"`` — race both like knossos.competition (the reference
    selects among these at checker.clj:90-93).

    The result carries the request's span summary and counters under
    ``spans`` and ``counts``.
    """
    known = {"witness", "cancel", "chunk", "cap_schedule", "explain",
             "checkpoint", "resume"}
    if kw.keys() - known:
        raise TypeError(f"unknown analysis options {kw.keys() - known}")
    with _trace.request() as req:
        r = _decide(model, history, algorithm, **kw)
    return {**r, **req.record()}


def _decide(model, history, algorithm: str, **kw) -> dict:
    try:
        packed = _prepare_mod.prepare(model, history)
    except UnsupportedHistory as e:
        if getattr(e, "kind", None) == "window" and algorithm != "tpu":
            # Past the device bitset (window > 64) the host search still
            # applies — Python int bitsets have no width limit. knossos
            # would grind on such histories too; grinding honestly beats
            # refusing (checker.clj:82-107 never gives up on width).
            try:
                packed = _prepare_mod.prepare(model, history,
                                              max_window=1 << 14)
            except UnsupportedHistory as e2:
                return {"valid?": "unknown", "error": str(e2),
                        "analyzer": "prepare"}
            from jepsen_tpu.lin import cpu

            ckw = {k: v for k, v in kw.items()
                   if k in ("witness", "cancel")}
            return cpu.check_packed(packed, **ckw)
        return {"valid?": "unknown", "error": str(e), "analyzer": "prepare"}

    if algorithm == "cpu":
        from jepsen_tpu.lin import cpu

        ckw = {k: v for k, v in kw.items() if k in ("witness", "cancel")}
        return cpu.check_packed(packed, **ckw)
    if algorithm == "tpu":
        return device_check_packed(packed, **kw)
    if algorithm == "competition":
        return _competition(packed, **kw)
    raise ValueError(f"unknown linearizability algorithm {algorithm!r}")


def device_check_packed(packed: PackedHistory, cancel=None, **kw) -> dict:
    """The device search, routed by history shape: the dense config-space
    bitmap engine (:mod:`jepsen_tpu.lin.dense`) when window and state count
    fit its bounds — including every crashed-op history within them — else
    the sparse sort-dedup frontier (:mod:`jepsen_tpu.lin.bfs`)."""
    from jepsen_tpu.lin import bfs, dense

    known = {"chunk", "cap_schedule", "explain", "checkpoint", "resume",
             "frontier", "frontier_row", "partial", "host_caps"}
    if kw.keys() - known:
        # e.g. snapshots= is dense-only: call dense.check_packed directly.
        raise TypeError(f"unknown device-check options {kw.keys() - known}")
    # Streaming incremental entry (frontier carry / partial verdicts,
    # jepsen_tpu.stream): always the sparse engine — the carried
    # frontier is in its multiword layout, which the dense config-space
    # bitmap cannot re-enter.
    incremental = kw.get("partial") or kw.get("frontier") is not None
    if not incremental and dense.plan(packed) is not None:
        # checkpoint/resume are sparse-engine options (dense histories
        # decide in seconds; there is nothing worth resuming).
        dkw = {k: v for k, v in kw.items() if k in ("chunk", "explain")}
        # The top-level "check" span anchors time attribution: every
        # dispatch/compile span nests inside it, and the trace report's
        # per-site rows sum against its wall time (doc/observability.md).
        with _trace.span("check", engine="dense", rows=int(packed.R),
                         window=int(packed.window)) as sp:
            r = dense.check_packed(packed, cancel=cancel, **dkw)
            sp.note(verdict=str(r.get("valid?")))
            return r
    with _trace.span("check", engine="sparse", rows=int(packed.R),
                     window=int(packed.window)) as sp:
        r = bfs.check_packed(packed, cancel=cancel, **kw)
        sp.note(verdict=str(r.get("valid?")))
        return r


def _competition(packed: PackedHistory, cancel=None, **kw) -> dict:
    """Race the device and host searches; the first *definite* verdict wins
    (knossos.competition/analysis semantics). A racer returning "unknown"
    (e.g. no device kernel for this model) does not end the race — only
    when both racers fail to decide is "unknown" returned. An external
    ``cancel`` event (e.g. a checker time budget) aborts both racers;
    the race also sets it internally to stop the loser."""
    from jepsen_tpu.lin import cpu

    cpu_kw = {k: v for k, v in kw.items() if k in ("witness",)}
    dev_kw = {k: v for k, v in kw.items()
              if k in ("chunk", "cap_schedule", "explain", "checkpoint",
                       "resume")}
    lock = threading.Lock()
    state: dict = {"result": None, "finished": 0}
    done = threading.Event()
    cancel = cancel if cancel is not None else threading.Event()

    def run(fn, name, fkw):
        try:
            r = fn(packed, cancel=cancel, **fkw)
        except Exception as e:  # noqa: BLE001 - loser may die, race decides
            r = {"valid?": "unknown", "error": f"{name}: {e!r}"}
        with lock:
            state["finished"] += 1
            if r.get("valid?") in (True, False):
                if not done.is_set():
                    state["result"] = r
                    done.set()
            else:
                if state["result"] is None and \
                        r.get("error") != "cancelled":
                    state["result"] = r  # fallback if nobody decides
                if state["finished"] == 2:
                    done.set()

    # Each racer runs in a copy of this context, so its spans reach the
    # open request.
    threads = [threading.Thread(target=contextvars.copy_context().run,
                                args=(run, fn, name, fkw))
               for fn, name, fkw in ((cpu.check_packed, "cpu", cpu_kw),
                                     (device_check_packed, "tpu", dev_kw))]
    for t in threads:
        t.start()
    done.wait()
    # Stop the loser (it checks `cancel` between rows/chunks) and join it —
    # an abandoned thread still inside XLA aborts the process at exit.
    cancel.set()
    for t in threads:
        t.join()
    with lock:
        if state["result"] is None:
            # Both racers were cancelled before deciding (e.g. an
            # external time budget fired): honest unknown.
            return {"valid?": "unknown", "error": "cancelled"}
        return dict(state["result"])
