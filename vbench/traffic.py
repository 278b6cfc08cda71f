"""The one traffic generator.  A mix is a data file, ``traffic/<mix>.json``.

Keys of a mix:

* ``loop``: ``"closed"`` (each client sends its next request when its
  last one is answered) or ``"open"`` (requests are due on a schedule,
  whatever the server does);
* ``clients`` (closed): the number of clients, a whole number or
  ``{"per_largest_bucket": k}``, k times the largest batch bucket of the
  configuration;
* ``arrivals`` / ``rate_per_s`` (open): ``"poisson"`` at that mean rate;
* ``sla_ms``: the latency limit each request carries (null: none);
* ``warm_s``: seconds of the same traffic before the window opens;
* ``bank``: how many distinct images the requests draw from;
* ``buckets`` (optional): the batch buckets of the configuration that
  the mix uses, and the only ones the server is built, compiled and
  probed with: a list, or ``"largest"`` for the largest alone (default:
  all of them).

Open-loop due times are fixed before any request is sent.  The gaps of a
Poisson schedule are the same multiset for every seed (the exponential
quantiles at evenly spaced probabilities) and the seed only orders them,
so every seed offers the same number of requests in the window.  The
seed also picks each request's image.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional, Sequence

import numpy as np

# images picked ahead for a closed loop; the pick wraps around after that
CLOSED_PICKS = 1 << 20


@dataclasses.dataclass(frozen=True)
class Plan:
    loop: str
    clients: int
    sla_ms: Optional[float]
    warm_s: float
    seconds: float
    bank: int
    # open loop: due time of request i, seconds from the window's start
    # (negative while warming up); None for a closed loop
    due: Optional[np.ndarray]
    picks: np.ndarray                    # image of request i (mod len)

    def image_of(self, i: int) -> int:
        return int(self.picks[i % len(self.picks)])

    @property
    def tail_s(self) -> float:
        """Seconds the open schedule runs past the window, so that the
        requests due at its end meet a queue in the same state."""
        return (self.sla_ms or 0.0) / 1e3


def poisson_gaps(rate_per_s: float, n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``n`` exponential gaps of mean 1/rate: the quantiles at
    probabilities (i + 0.5) / n, in the order ``rng`` shuffles them."""
    p = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-p) / float(rate_per_s)
    return rng.permutation(gaps)


def _clients(spec: Any, buckets: Sequence[int]) -> int:
    if isinstance(spec, Mapping):
        return int(spec["per_largest_bucket"]) * int(max(buckets))
    return int(spec)


def served_buckets(mix: Mapping[str, Any],
                   buckets: Sequence[int]) -> tuple:
    """The configuration's ``buckets`` that the mix uses."""
    want = mix.get("buckets")
    if want is None:
        return tuple(int(b) for b in buckets)
    if want == "largest":
        return (int(max(buckets)),)
    extra = set(want) - set(buckets)
    if extra:
        raise ValueError(f"the mix asks for buckets {sorted(extra)} that "
                         f"the configuration does not serve")
    return tuple(sorted(int(b) for b in want))


def plan(mix: Mapping[str, Any], seed: int, seconds: float,
         buckets: Sequence[int]) -> Plan:
    """The requests of one run of ``mix``, from ``seed``."""
    rng = np.random.default_rng([int(seed), 0x7A11])
    bank = int(mix.get("bank", 64))
    warm = float(mix.get("warm_s", 1.0))
    sla = mix.get("sla_ms")
    sla = None if sla is None else float(sla)
    loop = mix["loop"]
    if loop == "closed":
        picks = rng.integers(0, bank, size=CLOSED_PICKS, dtype=np.int32)
        return Plan("closed", _clients(mix["clients"], buckets), sla, warm,
                    float(seconds), bank, None, picks)
    if loop != "open":
        raise ValueError(f"unknown loop {loop!r}")
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    rate = float(mix["rate_per_s"])
    span = warm + float(seconds) + (sla or 0.0) / 1e3
    n = int(math.ceil(rate * span))
    due = np.cumsum(poisson_gaps(rate, n, rng)) - warm
    picks = rng.integers(0, bank, size=n, dtype=np.int32)
    return Plan("open", 0, sla, warm, float(seconds), bank, due, picks)
