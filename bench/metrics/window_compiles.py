"""XLA compilations (or loads from the compilation cache) inside the
window, counted by a ``jax.monitoring`` listener. The target is 0."""


def read(run):
    return run.compiles_in_window
