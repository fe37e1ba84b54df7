"""XLA programs compiled (or loaded from the persistent cache) inside the
window. Expected 0."""


def compute(run):
    return run.window_compiles
