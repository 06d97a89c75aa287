"""Plain reference for a CAS register under linearizability.

It decides what the checker must answer for each generated history, from
the events and the generator's certificate alone, and imports nothing of
the program:

- valid: the certificate is a total order of the ops that took effect,
  each placed between its invocation and its completion, and replaying
  it on a register that starts empty gives every ok op the result it
  returned. Such an order is the definition of linearizability, so the
  answer is ``valid? True``.
- invalid (one corrupted read): the read returns a value that no write
  or CAS ever stores and that is not the empty initial value, so no
  order can explain it, and the answer is ``valid? False``. Every other
  op is still explained by the certificate (a read changes no state), so
  each prefix of the history before that read's completion is
  linearizable with the read left pending. The first completion at which
  no order survives is therefore the corrupted read's, and that read is
  the op the checker has to name.

:func:`paths_fault` checks the counterexample paths the checker returns:
each a sequence of the history's own ops, none failed or repeated, that
is legal on the register step by step and ends in the state it claims.
"""

from __future__ import annotations


class CertificateError(ValueError):
    """The generator's certificate does not prove what it claims."""


def _pairs(events: list) -> dict:
    """invoke position -> completion position (None if never completed)."""
    open_: dict = {}
    comp: dict = {}
    for i, (t, _f, _v, p) in enumerate(events):
        if t == "invoke":
            open_[p] = i
            comp[i] = None
        else:
            comp[open_.pop(p)] = i
    return comp


def _step(state, f, arg, result):
    """Next state of the register, or raise if the op is illegal. As in
    knossos's CASRegister, a read that returned nil saw an unknown value
    and is legal in any state."""
    if f == "read":
        if result is not None and result != state:
            raise CertificateError(f"read saw {result!r}, state {state!r}")
        return state
    if f == "write":
        return arg
    cur, new = arg
    if cur != state:
        raise CertificateError(f"cas {arg!r} on state {state!r}")
    return new


def _certify(events: list, lin: list, skip: int | None = None) -> None:
    """Raise unless ``lin`` linearizes every op of ``events`` except the
    read invoked at ``skip``, which is left pending."""
    comp = _pairs(events)
    seen: set = set()
    state = None
    last = -1
    for inv, lp in lin:
        if inv == skip:
            continue
        t_inv, f, arg, _p = events[inv]
        c = comp.get(inv, "missing")
        if t_inv != "invoke" or c == "missing" or inv in seen:
            raise CertificateError(f"bad certificate entry {inv}")
        seen.add(inv)
        ctype = None if c is None else events[c][0]
        if ctype == "fail" or not inv < lp or lp <= last or \
                (ctype == "ok" and lp > c):
            raise CertificateError(f"op {inv} placed at {lp} outside "
                                   f"its interval")
        last = lp
        result = events[c][2] if ctype == "ok" else None
        if f == "read" and ctype != "ok":
            continue
        state = _step(state, f, arg, result)
    for inv, c in comp.items():
        if c is not None and events[c][0] == "ok" and inv not in seen \
                and inv != skip:
            raise CertificateError(f"ok op {inv} missing from certificate")


def expected(events: list, lin: list, corrupted: int | None) -> dict:
    """What the checker must answer: ``{"valid?": True}``, or for a
    corrupted read ``{"valid?": False, "op": {...}}`` naming that read by
    process, function, value and invocation index."""
    if corrupted is None:
        _certify(events, lin)
        return {"valid?": True}
    t, f, bad, proc = events[corrupted]
    if t != "ok" or f != "read" or bad is None:
        raise CertificateError(f"event {corrupted} is not an ok read")
    stored = {v for t_, f_, v, _ in events if t_ == "invoke" and f_ == "write"}
    stored |= {v[1] for t_, f_, v, _ in events
               if t_ == "invoke" and f_ == "cas"}
    if bad in stored:
        raise CertificateError(f"corrupted value {bad!r} is written")
    inv = next(i for i in range(corrupted - 1, -1, -1)
               if events[i][0] == "invoke" and events[i][3] == proc)
    _certify(events, lin, skip=inv)
    return {"valid?": False,
            "op": {"process": proc, "f": "read", "value": bad,
                   "index": inv}}


def op_mismatch(named, want: dict) -> bool:
    """True unless the checker's named op is the expected one."""
    if not isinstance(named, dict):
        return True
    return any(named.get(k) != v for k, v in want.items())


def paths_fault(events: list, paths) -> str | None:
    """Why a ``final-paths`` list is not a counterexample of the history:
    empty, or an entry that is not a legal run of the history's own ops
    ending in the state it names; None if every entry is one. A path
    starts where the checker's replay was anchored (a chunk boundary for
    the dense engine), so the register may hold any value there: each
    path has to be legal from at least one of them."""
    if not isinstance(paths, list) or not paths:
        return "no final paths"
    comp = _pairs(events)
    starts = {None} | {e[2] for e in events if e[0] == "invoke"
                       and e[1] == "write"}
    starts |= {e[2][1] for e in events if e[0] == "invoke" and e[1] == "cas"}
    for entry in paths:
        why = _path_fault(events, comp, starts, entry)
        if why:
            return why
    return None


def _path_fault(events: list, comp: dict, starts: set,
                path_entry) -> str | None:
    if not isinstance(path_entry, dict) or \
            not isinstance(path_entry.get("path"), list):
        return "malformed path"
    steps = []
    for op in path_entry["path"]:
        try:
            inv = op["index"]
            t_inv, f, arg, proc = events[inv]
        except (KeyError, IndexError, TypeError):
            return f"path op {op!r} is not in the history"
        c = comp.get(inv)
        if t_inv != "invoke" or op.get("f") != f or \
                op.get("process") != proc:
            return f"path op {op!r} does not match event {inv}"
        if c is not None and events[c][0] == "fail":
            return f"path op {inv} failed and cannot take effect"
        ok = c is not None and events[c][0] == "ok"
        if f != "read" and op.get("value") != arg:
            return f"path op {inv} has value {op.get('value')!r}"
        if f != "read" or ok:
            steps.append((inv, f, arg, events[c][2] if ok else None))
    if len({s[0] for s in steps}) != len(steps):
        return "path repeats an op"
    why = "no start state"
    for state in starts:
        try:
            for inv, f, arg, result in steps:
                state = _step(state, f, arg, result)
        except CertificateError as e:
            why = f"illegal from every start state ({e})"
            continue
        if state == path_entry.get("model"):
            return None
        why = f"path ends in {state!r}, claims {path_entry.get('model')!r}"
    return why
