"""witness_span_share: seconds inside the program's own ``witness`` span
(the counterexample's decode and tail replay, ``lin/witness.py``),
summed from the verdicts' ``spans`` over the window's calls, over the
window's seconds. The in-program twin of ``witness_share``. Nothing to
read where no verdict carries the span."""


def read(run):
    got = [c.result["spans"]["witness"]["s"] for c in run.calls
           if "witness" in c.result.get("spans", {})]
    return sum(got) / run.window_s if got else None
