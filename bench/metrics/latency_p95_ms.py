"""95th percentile, over every request due in the window and answered, of
the time from when it was due to its last bit's delivery (ms). In a closed
loop a request is due when its send starts; a frame's last bit comes with
its finish(). Nearest rank: the smallest sample with at least 95% at or
below it, taken over all requests, not a median of per-client tails."""

import math


def read(run):
    s = sorted(run.latencies_s)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)] * 1e3 if s else None
