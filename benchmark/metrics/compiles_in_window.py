"""compiles_in_window: XLA compiles (persistent-cache misses, counted by
``util.compile_meter``) while the window ran; set-up warms every shape,
so this should read 0."""


def read(run):
    return run.compiles_in_window
