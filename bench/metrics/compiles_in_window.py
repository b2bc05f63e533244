"""Launch path: executables JAX built or loaded inside the window (its
backend-compile events, persistent-cache loads included)."""


def read(run):
    return run.compiles
