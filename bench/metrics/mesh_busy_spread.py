"""Mesh: the spread of the cell's chips' busy time in the profiler trace,
(max - min) / window, in %. Near 0 where the chips share each launch's
lanes evenly; it grows where chip 0 carries work the others do not."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace["busy_s"][: run.chips]
    if not busy:
        return None
    return 100.0 * (max(busy) - min(busy)) / run.trace["window_s"]
