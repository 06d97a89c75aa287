"""prepare_span_share: seconds inside the program's own ``prepare`` span
(the whole host pack, ``lin/prepare.py``), summed from the verdicts'
``spans`` over the window's calls, over the window's seconds. The
in-program twin of ``pack_share``. Nothing to read where no verdict
carries the span."""


def read(run):
    got = [c.result["spans"]["prepare"]["s"] for c in run.calls
           if "prepare" in c.result.get("spans", {})]
    return sum(got) / run.window_s if got else None
