"""Launch: the share of the lanes the kernels ran in the window that held
no real block (``SessionPool`` counters ``lanes_real`` and
``lanes_launched``: the power-of-two/shard budget and the backend's lane
tile), in %."""


def read(run):
    c = getattr(run, "counters", None) or {}
    if not c.get("lanes_launched"):
        return None
    return 100.0 * (1.0 - c["lanes_real"] / c["lanes_launched"])
