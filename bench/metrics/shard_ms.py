"""Mesh: self time of the ``pbvd.shard`` spans in the window (a mesh
launch's framed lanes placed onto the chips) over the ``pbvd.launch`` spans
that started in it, in ms per launch; nothing where no lane was placed."""

from spans import PREFIX, per_launch_ms


def read(run):
    if PREFIX + "shard" not in ((run.trace or {}).get("spans") or {}):
        return None
    return per_launch_ms(run, PREFIX + "shard")
