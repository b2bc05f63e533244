"""Failure taxonomy + deterministic fault injection for the serving stack.

The source paper's block independence is what makes per-stream isolation
cheap: every PBVD block decodes from its own overlapped symbol window, so a
poisoned stream can be carved out of a coalesced launch and the survivors
relaunched bit-exact.  This module gives the serving layer the vocabulary to
do that:

* :class:`DecodeError` — root of the serving failure hierarchy.

  * :class:`StreamError` — the *stream* is at fault (non-finite soft
    symbols, shape-invalid chunks, a lane-group that reproducibly kills the
    launch).  Quarantining the stream fixes the batch.
  * :class:`DispatchError` — the *launch* is at fault (compile failure,
    runtime launch error, device loss).  Retrying — possibly on a rebuilt
    mesh — is the right response; the streams are innocent.

    * :class:`MeshLost` — a device-loss dispatch failure carrying how many
      chips died, so the service can :func:`plan a rescale
      <repro.launch.elastic.plan_rescale>`.
  * :class:`CapacityError` — the *service* is at fault (admission budget or
    slab arena exhausted).  Waiting, shedding, or resizing fixes it.
    ``Backpressure`` (serve_async) and ``SlabExhausted`` (slab) subclass it.

    * :class:`ShedError` — capacity stayed exhausted past the shed
      deadline; the admission was dropped rather than parked forever.

:class:`SymbolError` subclasses both :class:`StreamError` and
``ValueError`` so engine-boundary validation keeps its historical
``ValueError`` contract while the service can catch one class for every
per-stream cause.

:class:`FaultInjector` deterministically injects each failure class at the
admission / slab / dispatch / mesh boundaries under a seeded schedule, and
:class:`RetryPolicy` bounds the retry/backoff loop around dispatch.  Both
are pure host-side bookkeeping: no jax imports, reproducible under fake
clocks.  See DESIGN.md §14 for the full failure model.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from collections.abc import Iterable, Mapping

import numpy as np

__all__ = [
    "DecodeError",
    "StreamError",
    "SymbolError",
    "DispatchError",
    "IntegrityError",
    "MeshLost",
    "CapacityError",
    "ShedError",
    "nonfinite_error",
    "check_finite_symbols",
    "RetryPolicy",
    "FaultInjector",
    "FAULT_SITES",
]


class DecodeError(RuntimeError):
    """Root of the serving failure taxonomy (DESIGN.md §14)."""


class StreamError(DecodeError):
    """The stream is at fault; quarantining it heals the batch.

    ``stream`` (optional) names the offending stream for log lines; the
    underlying exception, when one exists, rides along as ``__cause__``.
    """

    def __init__(self, message: str, *, stream: object | None = None):
        super().__init__(message)
        self.stream = stream


class SymbolError(StreamError, ValueError):
    """Shape- or value-invalid symbols at the engine boundary.

    Also a ``ValueError`` so pre-taxonomy callers that caught the engine's
    historical validation errors keep working unchanged.
    """

    def __init__(self, message: str, *, stream: object | None = None):
        # ValueError.__init__ via StreamError's super() chain only stores
        # args; run StreamError's to also pin the stream attribute.
        StreamError.__init__(self, message, stream=stream)


class IntegrityError(StreamError):
    """Delivered bits failed the re-encode integrity screen.

    Raised by the serving layer's end-to-end sentinel
    (:class:`repro.launch.journal.IntegritySentinel`): the delivered block,
    re-encoded with the stream's convolutional code, agrees with the received
    hard decisions on fewer symbols than the path-metric-implied bound allows
    — the signature of silent data corruption between the kernel and the
    delivery queue, not of channel noise.  ``agreement`` carries the measured
    fraction and ``bound`` the threshold it fell below.
    """

    def __init__(
        self,
        message: str,
        *,
        stream: object | None = None,
        agreement: float | None = None,
        bound: float | None = None,
    ):
        super().__init__(message, stream=stream)
        self.agreement = agreement
        self.bound = bound


class DispatchError(DecodeError):
    """The launch is at fault; retry (possibly on a rebuilt mesh)."""


class MeshLost(DispatchError):
    """Device loss mid-dispatch; carries the casualty count for rescale."""

    def __init__(self, message: str, *, lost_chips: int = 1):
        super().__init__(message)
        self.lost_chips = int(lost_chips)


class CapacityError(DecodeError):
    """The service is out of room; wait, shed, or resize."""


class ShedError(CapacityError):
    """Capacity stayed exhausted past the shed deadline; admission dropped."""


def nonfinite_error(where: str, n_bad: int, n_total: int) -> SymbolError:
    """Uniform engine-boundary rejection for NaN/Inf soft symbols.

    Mirrors :func:`repro.kernels.registry.knob_error`'s shape — name the
    boundary, the offending value, and what IS supported — so every
    validation error in the repo reads the same way.
    """
    return SymbolError(
        f"{where} does not accept non-finite soft symbols: {n_bad} of "
        f"{n_total} values are NaN/Inf; supported symbol values: finite "
        f"floats (or pre-quantized integers).  A single non-finite symbol "
        f"corrupts the path metrics of every stream coalesced into the "
        f"same launch, so it is refused at the boundary."
    )


def check_finite_symbols(y, where: str) -> None:
    """Raise :func:`nonfinite_error` if a float symbol array holds NaN/Inf.

    Integer arrays (pre-quantized symbols) pass through untouched, as do
    jax tracers — validation is an eager-boundary concern and abstract
    values have no concrete entries to check.
    """
    import jax  # local: the failure taxonomy itself imports no jax

    if isinstance(y, jax.core.Tracer):
        return
    arr = np.asarray(y)
    if not np.issubdtype(arr.dtype, np.floating):
        return
    bad = ~np.isfinite(arr)
    if bad.any():
        raise nonfinite_error(where, int(bad.sum()), int(arr.size))


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry with exponential backoff for dispatch failures.

    ``delay_s(attempt)`` is a pure function of the attempt index so the
    whole retry schedule is deterministic under an injected fake clock:
    the service arms ``retry_at = clock() + delay_s(k)`` and simply refuses
    to re-dispatch until the clock passes it — no real sleeping in the
    dispatch path.
    """

    max_retries: int = 3
    backoff_s: float = 0.002
    multiplier: float = 2.0
    max_backoff_s: float = 0.25

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if self.backoff_s < 0.0 or self.max_backoff_s < 0.0:
            raise ValueError("backoff_s and max_backoff_s must be >= 0")
        if self.multiplier < 1.0:
            raise ValueError(f"multiplier must be >= 1, got {self.multiplier}")

    def delay_s(self, attempt: int) -> float:
        """Backoff before retry ``attempt`` (0-indexed), in seconds."""
        if attempt < 0:
            raise ValueError(f"attempt must be >= 0, got {attempt}")
        return float(min(self.backoff_s * self.multiplier**attempt, self.max_backoff_s))


FAULT_SITES = (
    "admission",
    "slab",
    "dispatch",
    "mesh",
    "stream_poison",
    # new sites append at the END: per-site rng streams are seeded by the
    # site's index in this tuple, so reordering would silently reshuffle
    # every rate-based chaos schedule
    "decode_corrupt",
)


class FaultInjector:
    """Deterministic fault injection at the serving-stack boundaries.

    Two scheduling modes, combinable per site:

    * ``schedule={"dispatch": {2, 9}}`` — fire on exactly the 2nd and 9th
      *consultation* of the ``dispatch`` site (0-indexed).  Fully
      deterministic regardless of event-loop interleaving; what the chaos
      tests use.
    * ``rates={"slab": 0.05}`` — fire i.i.d. with probability 0.05 per
      consultation, from a per-site ``np.random.default_rng([seed, site])``
      stream.  Deterministic for a fixed consultation order; what the
      degraded-mode benchmark uses.

    Sites (``FAULT_SITES``):

    * ``"admission"``  — admission-time validation failure (shape-invalid
      symbols): the sending stream is poisoned.
    * ``"slab"``       — synthetic ``SlabExhausted`` on a page reservation.
    * ``"dispatch"``   — transient launch failure; absorbed by retry.
    * ``"mesh"``       — device loss (``MeshLost(lost_chips=...)``);
      triggers the rescale/meshless fallback.
    * ``"stream_poison"`` — the Nth ``open()``-ed stream carries symbols
      that reproducibly kill any launch containing them; isolated by
      bisection.
    * ``"decode_corrupt"`` — silent data corruption: one bit of a freshly
      delivered block is flipped AFTER the kernel ran (consulted once per
      stream-with-delivery per dispatch); caught by the re-encode
      integrity sentinel, never by launch-level validation.

    ``counts[site]`` is how often a site was consulted, ``fired[site]`` how
    often it injected — both live on the instance for test assertions.
    """

    def __init__(
        self,
        seed: int = 0,
        *,
        schedule: Mapping[str, Iterable[int]] | None = None,
        rates: Mapping[str, float] | None = None,
        mesh_lost_chips: int = 1,
    ):
        self.seed = int(seed)
        self.schedule = {k: frozenset(int(i) for i in v) for k, v in (schedule or {}).items()}
        self.rates = {k: float(v) for k, v in (rates or {}).items()}
        for site in (*self.schedule, *self.rates):
            if site not in FAULT_SITES:
                raise ValueError(
                    f"unknown fault site {site!r}; supported sites: {FAULT_SITES}"
                )
        for site, rate in self.rates.items():
            if not 0.0 <= rate <= 1.0:
                raise ValueError(f"rate for {site!r} must be in [0, 1], got {rate}")
        self.mesh_lost_chips = int(mesh_lost_chips)
        self.counts: Counter[str] = Counter()
        self.fired: Counter[str] = Counter()
        self._rngs = {
            site: np.random.default_rng([self.seed, i])
            for i, site in enumerate(FAULT_SITES)
        }

    def fire(self, site: str) -> bool:
        """Consult ``site``; True means the caller must inject the fault."""
        if site not in FAULT_SITES:
            raise ValueError(
                f"unknown fault site {site!r}; supported sites: {FAULT_SITES}"
            )
        idx = self.counts[site]
        self.counts[site] += 1
        hit = idx in self.schedule.get(site, ())
        rate = self.rates.get(site, 0.0)
        if not hit and rate > 0.0:
            hit = bool(self._rngs[site].random() < rate)
        if hit:
            self.fired[site] += 1
        return hit
