"""Delivery: self time of the ``pbvd.deliver`` spans in the window (each
member's bits copied to the host and its session committed, slab prefix
dropped) over the ``pbvd.launch`` spans that started in it, in ms per
launch."""

from spans import per_launch_ms


def read(run):
    return per_launch_ms(run, "pbvd.deliver")
