"""Seeded open-loop request generator for the serving cells.

Copied from the program's ``serve/trace.py`` (split payload and arrival
streams, open-loop replay that counts a shed submit and never retries) so
that the yardstick does not move with the program, and extended with
lognormal (heavy-tailed) prompt and output lengths.

Every seed gets the same work: the prompt lengths, output lengths and
arrival times, in their order, are drawn from the mix's own
``shape_seed``; the run's seed draws the token ids (and, in the drivers,
the weights). With a few dozen heavy-tailed requests in a window, the
order in which long and short requests arrive moves the tails by a fifth
from one order to the next (measured on the chip), far more than two runs
of one order differ; so the order is part of the mix, and the rate only
rescales the arrival times.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, List, Sequence, Tuple

import numpy as np


@dataclass(frozen=True)
class Item:
    """One request: due offset from the window start, prompt, output."""

    due_s: float
    prompt: Tuple[int, ...]
    max_new_tokens: int


def _lognormal_lengths(rng: np.random.Generator, n: int, spec: dict
                       ) -> np.ndarray:
    """``n`` lengths, lognormal around ``median`` with ``sigma``, clipped
    to ``[min, max]``."""
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _seed_seq(seed: int, stream: int) -> np.random.Generator:
    # seeds may exceed 32 bits: split into 32-bit words for SeedSequence
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def count_for(rate: float, seconds: float) -> int:
    """Requests due in a window of ``seconds`` at ``rate`` req/s."""
    return max(1, int(round(rate * seconds)))


def generate(mix: dict, seed: int, seconds: float, vocab_size: int,
             rate: float = None) -> List[Item]:
    """The window's requests for one seed, in due order.

    ``mix`` holds ``rate`` (req/s), ``arrivals`` (``"poisson"``),
    ``prompt`` and ``output`` length specs (``dist`` ``"lognormal"``,
    ``median``, ``sigma``, ``min``, ``max``) and ``shape_seed``. Exactly
    ``count_for(rate, seconds)`` requests are due in ``[0, seconds)``.
    """
    rate = mix["rate"] if rate is None else rate
    n = count_for(rate, seconds)
    for key in ("prompt", "output"):
        if mix[key]["dist"] != "lognormal":
            raise ValueError(f"unknown {key} length dist "
                             f"{mix[key]['dist']!r}")
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    shape = _seed_seq(mix["shape_seed"], 0)
    plen = _lognormal_lengths(shape, n, mix["prompt"])
    olen = _lognormal_lengths(shape, n, mix["output"])
    gaps = shape.exponential(1.0, size=n)
    # the first request is due at 0 and the gaps span the window exactly,
    # so the window offers n requests at the mix's mean rate
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / float(np.sum(gaps))
    payload = _seed_seq(seed, 2)
    return [Item(due_s=float(d),
                 prompt=tuple(int(v) for v in
                              payload.integers(0, vocab_size, size=int(p))),
                 max_new_tokens=int(o))
            for d, p, o in zip(due, plen, olen)]


def replay(submit: Callable, items: Sequence[Item], t0: float,
           make_request: Callable[[Item], object], *,
           clock: Callable[[], float] = time.monotonic,
           sleep: Callable[[float], None] = time.sleep,
           between: Callable[[float], None] = None):
    """Open loop: submit each item at ``t0 + due_s`` however far behind
    the server is. Returns ``(futures, lateness_s)``: one entry per item,
    ``None`` for a submit that raised (shed or refused), and how late each
    submit went out. ``between(now)`` runs before each wait (the traced
    run starts and stops the profiler there)."""
    futures, late = [], []
    for it in items:
        if between is not None:
            between(clock())
        delay = t0 + it.due_s - clock()
        if delay > 0:
            sleep(delay)
        late.append(max(0.0, clock() - (t0 + it.due_s)))
        try:
            futures.append(submit(make_request(it)))
        except Exception:          # shed or refused: a failed request
            futures.append(None)
    return futures, late
