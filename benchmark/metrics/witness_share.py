"""witness_share: seconds inside ``lin.witness.tail_replay`` and
``tail_replay_sparse`` (the benchmark's ``witness`` span) over the
window's seconds. Nothing to read where the window replayed no
counterexample."""


def read(run):
    s = run.spans.get("witness")
    return s / run.window_s if s else None
