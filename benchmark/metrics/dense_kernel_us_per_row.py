"""dense_kernel_us_per_row: device time of the dense engine's Pallas
kernel in the traced window, over the return rows the window swept (all
of a valid history's, an invalid one's up to its dead row), in
microseconds. The kernel is found by the name the
trace gives it: ``%pallas_chunk.N``, after ``lin/dense_pallas.py``'s
``pallas_chunk`` (the ``pallas_call`` has no explicit name yet). Nothing
to read where the trace holds no such kernel."""

KERNEL = "pallas_chunk"


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.kernel_ns(lambda name: KERNEL in name)
    rows = sum(c.result["dead-row"] + 1 if c.result["valid?"] is False
               else c.rows for c in run.decided
               if c.result.get("analyzer") == "tpu-dense")
    if ns <= 0 or rows <= 0:
        return None
    return ns / 1e3 / rows
