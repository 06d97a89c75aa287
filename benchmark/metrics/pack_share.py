"""pack_share: seconds inside ``lin.prepare.prepare`` (the benchmark's
``pack`` span) over the window's seconds."""


def read(run):
    return run.spans.get("pack", 0.0) / run.window_s
