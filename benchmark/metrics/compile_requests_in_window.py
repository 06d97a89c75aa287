"""compile_requests_in_window: in-memory jit misses while the window ran,
each a request to the compile cache (``util.compile_meter``; persistent
cache hits count too), summed from the verdicts' ``counts``. Set-up
warms every shape, so this should read 0. Nothing to read where no
verdict carries the counter."""


def read(run):
    got = [c.result["counts"]["compile_requests"] for c in run.calls
           if "compile_requests" in c.result.get("counts", {})]
    return sum(got) if got else None
