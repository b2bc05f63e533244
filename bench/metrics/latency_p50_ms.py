"""Median, over every request due in the window and answered, of the time
from when it was due to its last bit's delivery (ms). In a closed loop a
request is due when its send starts; a frame's last bit comes with its
finish(). Nearest rank: the smallest sample with at least half at or
below it."""

import math


def read(run):
    s = sorted(run.latencies_s)
    return s[max(0, math.ceil(0.50 * len(s)) - 1)] * 1e3 if s else None
