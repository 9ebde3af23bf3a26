"""Programs XLA handed out inside the window, compiled or loaded from the
persistent cache (jax's ``backend_compile_duration`` events): work that
warm-up left undone."""


def read(run):
    return run.compiles_in_window
