"""Batching (``SessionPool``): real blocks delivered in the window over the
kernel launches the service counted in it (its ``launches`` counter)."""


def read(run):
    return run.blocks / run.launches if run.launches else None
