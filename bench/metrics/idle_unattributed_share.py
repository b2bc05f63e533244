"""Device: of each chip's idle time in the window, the share during which no
``pbvd.*`` span of the program is open on any host line, averaged over the
cell's chips, in %. What is left is idle time that no layer of the program
accounts for: the event loop, the benchmark's clients, the tracer."""


def read(run):
    t = run.trace or {}
    idle, free = t.get("idle_s"), t.get("idle_unattributed_s")
    if idle is None or free is None:
        return None
    shares = [f / i for i, f in zip(idle[: run.chips], free[: run.chips]) if i > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None
