"""Programs compiled or loaded from the compile cache inside the window
(JAX's backend-compile events). Set-up warms every shape the traffic can
reach, so this reads 0 unless a shape escaped it."""


def read(run):
    return run.compiles
