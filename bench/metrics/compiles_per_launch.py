"""Launch path: executables JAX built or loaded in the window (its
backend-compile events, persistent-cache loads included) over the kernel
launches the service counted in it. 0 once each lane shape's launch is
built once; about 3 where every mesh launch is traced and compiled anew."""


def read(run):
    return run.compiles / run.launches if run.launches else None
