"""dense_host_share: self seconds of the program's ``dense.prep`` spans
(the dense engine's host work before and between its kernel enqueues,
``lin/dense.py``, while the device idles), summed from the verdicts'
``spans`` over the window's calls, over the window's seconds. Nothing to
read where no verdict carries the span."""


def read(run):
    got = [c.result["spans"]["dense.prep"]["self_s"] for c in run.calls
           if "dense.prep" in c.result.get("spans", {})]
    return sum(got) / run.window_s if got else None
