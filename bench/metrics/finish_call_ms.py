"""Session flush (``AsyncStream.finish`` -> ``PooledSession.finish``): median
host-clock time of the finish() calls completed in the window (ms)."""

import statistics


def read(run):
    return statistics.median(run.finish_s) * 1e3 if run.finish_s else None
