"""The decode's work, counted from the algorithm, and the chip's peaks.

Work is counted from the code (K, R), the geometry (D, L) and the real
blocks decoded, whatever the program does to get there:

* operations: each of the T real stages of a block updates every one of
  the 2^(K-1) states with one add-compare-select, counted as 4 operations
  (two additions, one comparison, one selection). In a long stream a
  block's window spans T = D + 2L received stages; a terminated stream
  shorter than that (a frame) has only its own S stages, so T = min(D + 2L, S);
* bytes: the block's received symbols in (T stages x R symbols x 1 byte at
  q <= 8) and its decoded bits out (min(D, S) / 8 bytes).

Padded lanes, padded stages, survivors spilled to memory or a change of
backend add time, not work, so they read as a lower share.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["block_work", "least_time_s", "peaks_for"]

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def block_work(
    K: int, R: int, D: int, L: int, q: int, stream_stages: int | None = None
) -> tuple[float, float]:
    """(operations, bytes) that decoding one block needs, in a stream of
    ``stream_stages`` stages (None: unbounded)."""
    T, out = D + 2 * L, D
    if stream_stages is not None:
        T, out = min(T, stream_stages), min(out, stream_stages)
    ops = 4.0 * (1 << (K - 1)) * T
    sym_bytes = 1 if q <= 8 else 2
    return ops, T * R * sym_bytes + out / 8.0


def peaks_for(device_kind: str, path: Path = PEAKS) -> dict:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def least_time_s(blocks: float, work: tuple[float, float], peaks: dict) -> tuple[float, str]:
    """The least time one chip needs for ``blocks`` blocks, and which term
    binds (``"compute"`` or ``"bytes"``)."""
    ops, nbytes = work
    t_ops = blocks * ops / peaks["ops_per_s"]
    t_bytes = blocks * nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "bytes")
