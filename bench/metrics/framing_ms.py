"""Session state and framing: self time of the ``pbvd.frame`` spans in the
window (host windows read from the slab, copied to the device, quantized
and gathered into lanes) over the ``pbvd.launch`` spans that started in it,
in ms per launch."""

from spans import per_launch_ms


def read(run):
    return per_launch_ms(run, "pbvd.frame")
