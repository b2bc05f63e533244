"""Payload bits delivered to clients in the window over the window's
seconds (host clock), in Mb/s. Tail bits are not payload."""


def read(run):
    return run.payload_bits / run.window_s / 1e6
