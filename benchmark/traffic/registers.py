"""The benchmark's own CAS-register history generator.

A copy of the semantics of ``jepsen_tpu/lin/synth.py``'s
``generate_register_history`` and ``corrupt_history`` (bench.py's
headline and ``wide_window_c30`` calls), kept here so that a later PR
that changes the program's generator cannot change the yardstick. It
imports nothing of the program: events are plain tuples
``(type, f, value, process)``, and the harness turns them into the
checker's ``History``.

Each history is linearizable by construction: the simulated register
applies an op at the moment it completes. The generator records that
order as a certificate, ``lin``: one ``(invoke_pos, lin_pos)`` pair per
op that took effect, in order. A crashed op (``info``) takes effect or
not on a coin flip, and its process is re-incarnated
(``process + concurrency``), as Jepsen's runner does.
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field

READ, WRITE, CAS = "read", "write", "cas"


@dataclass
class Generated:
    """One history as events plus the linearization that made it."""

    events: list                       # (type, f, value, process)
    lin: list                          # (invoke_pos, lin_pos), in order
    corrupted: int | None = None       # event position of a corrupted read
    meta: dict = field(default_factory=dict)


def _apply(value, f, arg):
    """(new value, result, ok) of ``f(arg)`` on a register holding
    ``value``; a CAS whose precondition fails does not take effect."""
    if f == READ:
        return value, value, True
    if f == WRITE:
        return arg, arg, True
    cur, new = arg
    if cur == value:
        return new, arg, True
    return value, arg, False


def generate(n_ops: int, concurrency: int, seed: int, value_range: int,
             crash_prob: float, max_crashes: int,
             accept=None) -> Generated | None:
    """A linearizable-by-construction CAS-register history of ``n_ops``
    invocations over ``concurrency`` processes and values
    ``0..value_range-1``, with up to ``max_crashes`` crashed ops.

    ``accept``, a predicate on :func:`strata`'s dict, is asked as soon as
    the history's strata are settled (its last crash, or its end); where
    it says no, the draw stops there and returns None. A history it
    accepts is the one the same seed gives without it."""
    rng = random.Random(seed)
    rand, randrange, choice = rng.random, rng.randrange, rng.choice
    value = None
    events: list = []
    lin: list = []
    procs = list(range(concurrency))
    pending: dict = {}      # process -> (f, arg, invoke_pos)
    crashes = 0
    invoked = 0
    fs = (READ, WRITE, CAS)
    while invoked < n_ops or pending:
        if invoked < n_ops and len(pending) < concurrency and \
                (not pending or rand() < 0.6):
            proc = choice([p for p in procs if p not in pending])
            f = choice(fs)
            if f == READ:
                arg = None
            elif f == WRITE:
                arg = randrange(value_range)
            else:
                arg = [randrange(value_range), randrange(value_range)]
            pending[proc] = (f, arg, len(events))
            events.append(("invoke", f, arg, proc))
            invoked += 1
            continue
        proc = choice(list(pending))
        f, arg, inv = pending.pop(proc)
        pos = len(events)
        if crashes < max_crashes and rand() < crash_prob:
            if rand() < 0.5:
                value, _, ok = _apply(value, f, arg)
                if ok:
                    lin.append((inv, pos))
            events.append(("info", f, arg, proc))
            crashes += 1
            procs[procs.index(proc)] = proc + concurrency
            if crashes == max_crashes and accept is not None and \
                    not accept(_strata(events)):
                return None
            continue
        value, result, ok = _apply(value, f, arg)
        if ok:
            lin.append((inv, pos))
            events.append(("ok", f, result, proc))
        else:
            events.append(("fail", f, arg, proc))
    if crashes < max_crashes and accept is not None and \
            not accept(_strata(events)):
        return None
    return Generated(events, lin, meta={"crashes": crashes})


def rows(g: Generated) -> int:
    """Return rows: the ok completions, in history order (the checker's
    packed rows)."""
    return sum(1 for e in g.events if e[0] == "ok")


def corrupt(g: Generated, row: int) -> Generated:
    """The history with one ok read changed to a value that no op ever
    writes: the first ok read at or after return row ``row`` (the last
    one if none follows). The certificate no longer holds for that
    read."""
    oks = [i for i, e in enumerate(g.events) if e[0] == "ok"]
    reads = [i for i in oks[row:] if g.events[i][1] == READ] or \
        [i for i in oks if g.events[i][1] == READ][-1:]
    i = reads[0]
    t, f, v, p = g.events[i]
    events = list(g.events)
    events[i] = (t, f, (v if v is not None else 0) + 1000, p)
    return Generated(events, g.lin, corrupted=i, meta=dict(g.meta))


def strata(g: Generated) -> dict:
    """The properties a pool is stratified on. ``crashed_mutators``, the
    crashed writes and CASes, sets the checker's window: a crashed read
    takes no effect, a crashed mutator may take effect at any later time,
    so it holds its place for the rest of the history."""
    return _strata(g.events)


def _strata(events: list) -> dict:
    return {"crashed_mutators": sum(1 for e in events
                                    if e[0] == "info" and e[1] != READ)}


def crashes_as_failed(events: list) -> list:
    """A control's input: every crashed op rewritten as failed, i.e. as
    certainly not having taken effect. This breaks the guarantee that a
    crashed op is indeterminate."""
    return [("fail",) + e[1:] if e[0] == "info" else e for e in events]


def crashes_as_ok(events: list) -> list:
    """A control's input: every crashed op rewritten as having completed
    ok, i.e. as certainly having taken effect (a read as having returned
    nothing known). This breaks the same guarantee the other way."""
    return [("ok",) + e[1:] if e[0] == "info" else e for e in events]
