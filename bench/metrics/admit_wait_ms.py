"""Client / admission (``AsyncDecodeService._admit``): seconds the chunks
admitted in the window spent parked behind the pending-block cap or slab
pages (the service's ``admit_wait_s`` counter, on its own clock) over the
chunks admitted (its ``admits``), in ms."""


def read(run):
    c = getattr(run, "counters", None) or {}
    if not c.get("admits"):
        return None
    return c["admit_wait_s"] / c["admits"] * 1e3
