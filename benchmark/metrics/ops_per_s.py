"""ops_per_s: operations of every history decided in the window, over
the window's seconds (host clock). The window ends when the call in
flight at ``--seconds`` returns, so it holds whole calls only."""


def read(run):
    return sum(c.ops for c in run.decided) / run.window_s
