"""Programs compiled or loaded from the persistent cache inside the window
(JAX backend-compile events); set-up warms every program, so 0."""


def read(run):
    return run.window_compiles
