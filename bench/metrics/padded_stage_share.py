"""Session state and framing: over the real lanes launched in the window,
the share of their T = D + 2L stages that are framing's zero padding and
carry no received symbol (``SessionPool`` counters ``stages_real`` and
``stages_launched``), in %."""


def read(run):
    c = getattr(run, "counters", None) or {}
    if not c.get("stages_launched"):
        return None
    return 100.0 * (1.0 - c["stages_real"] / c["stages_launched"])
