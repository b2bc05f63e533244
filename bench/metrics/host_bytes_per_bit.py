"""Launch: bytes the launches of the window moved between host and device
(``SessionPool`` counters ``h2d_bytes``, the framed host windows, and
``d2h_bytes``, the bits copied back) per payload bit delivered in it."""


def read(run):
    c = getattr(run, "counters", None) or {}
    if "h2d_bytes" not in c or "d2h_bytes" not in c or run.payload_bits <= 0:
        return None
    return (c["h2d_bytes"] + c["d2h_bytes"]) / run.payload_bits
