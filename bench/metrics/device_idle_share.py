"""Device: 1 - busy / window from the profiler trace, for each of the cell's
chips, averaged over them, in %."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace["busy_s"][: run.chips]
    if not busy:
        return None
    w = run.trace["window_s"]
    return 100.0 * sum(1.0 - b / w for b in busy) / len(busy)
