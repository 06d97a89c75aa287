"""prepare_pair_share: self seconds of the program's ``prepare.pair``
span (``list(history)`` and the invoke/return pairing in
``lin/prepare.py``), summed from the verdicts' ``spans`` over the
window's calls, over the window's seconds. Nothing to read where no
verdict carries the span."""


def read(run):
    got = [c.result["spans"]["prepare.pair"]["self_s"] for c in run.calls
           if "prepare.pair" in c.result.get("spans", {})]
    return sum(got) / run.window_s if got else None
