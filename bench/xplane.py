"""Reduction of a profiler trace (``.xplane.pb``) to the numbers a run reports.

* The window is the host span named ``bench.window``, which the harness
  opens when the measured window starts and closes when it ends.
* A device's busy time is the union of the intervals of its operations
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) inside the
  window; its idle share is 1 - busy / window.
* Kernel time is the summed time of the operations that are Pallas (Mosaic)
  kernels: on the TPU each is an XLA custom call to ``tpu_custom_call``,
  and the event's name is its HLO text.
* Each idle gap of a device is named by the host event that overlaps it
  most, among the benchmark's own spans and JAX's dispatch events.
"""

from __future__ import annotations

import collections
import glob
import os

__all__ = ["union", "clip", "gaps", "name_gap", "short_name", "reduce_trace", "find_xplane"]

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def clip(intervals, lo: float, hi: float):
    """Intervals cut to [lo, hi]; empty ones dropped."""
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals):
    """Sorted disjoint intervals covering the same points."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def gaps(busy, lo: float, hi: float):
    """The idle intervals of [lo, hi] between sorted disjoint ``busy`` ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def name_gap(gap, host_events) -> str:
    """The name of the innermost host event covering at least half of
    ``gap`` (the shortest such event), else of the one overlapping it most;
    ``"(no host event)"`` where none overlaps it."""
    a, b = gap
    half, inner, widest = (b - a) / 2, None, None
    for name, s, e in host_events:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        if ov >= half and (inner is None or e - s < inner[0]):
            inner = (e - s, name)
        if widest is None or ov > widest[0]:
            widest = (ov, name)
    if inner is not None:
        return inner[1]
    return widest[1] if widest is not None else "(no host event)"


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {files}")
    return files[0]


def _is_kernel(name: str) -> bool:
    """A Pallas kernel: on the TPU, a custom call to ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in name


def short_name(name: str) -> str:
    """An operation's name without its operands: the HLO instruction and
    its result shape (``%acs_forward_pallas.1 = (s32[640,2,4096]...``),
    cut at 96 characters."""
    head = name.split("{", 1)[0].strip()
    return head[:96]


def reduce_trace(path: str, top: int = 10) -> dict:
    """Busy and kernel seconds per device, the window, the device operations
    that took most time and the longest idle gaps, from one trace file.

    Times are in seconds. ``busy_s`` and ``kernel_s`` are lists with one
    entry per device plane, in device order; ``None`` where the trace holds
    no window span or no device plane.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host_events = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == WINDOW:
                        window = (s, e)
                    else:
                        host_events.append((ev.name, s, e))
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
            devices.append((plane.name, ops))
    if window is None or not devices:
        return None
    lo, hi = window
    devices.sort(key=lambda d: int(d[0][len(DEVICE_PREFIX):]))
    busy_s, kernel_s, op_time = [], [], collections.Counter()
    kernel_time = collections.Counter()
    all_gaps = []
    for _, ops in devices:
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_s.append(sum(b - a for a, b in busy) * 1e-9)
        k = 0.0
        for name, s, e in ops:
            t = max(0.0, min(e, hi) - max(s, lo)) * 1e-9
            op_time[short_name(name)] += t
            if _is_kernel(name):
                k += t
                kernel_time[short_name(name)] += t
        kernel_s.append(k)
        all_gaps.extend(gaps(busy, lo, hi))
    all_gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    host_events = [h for h in host_events if min(h[2], hi) > max(h[1], lo)]
    idle = [[name_gap(g, host_events), (g[1] - g[0]) * 1e-9] for g in all_gaps[:top]]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "kernel_s": kernel_s,
        "kernel_by_name": dict(kernel_time),
        "device_ops": [[n, t] for n, t in op_time.most_common(top)],
        "idle_gaps": idle,
    }
