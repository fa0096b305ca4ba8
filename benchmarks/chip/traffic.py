"""The one traffic generator: it reads a traffic file's parameters and
makes the requests of a run from the seed.

Keys of a traffic file:

* ``loop``: ``"open"`` (requests fall due on a schedule, whatever the
  server does) or ``"closed"`` (the client keeps ``queued_images``
  images waiting in the server's queue);
* ``rate_per_s`` (open): the offered rate. The gaps between arrivals are
  the n quantiles (i + 0.5) / n of the exponential distribution of that
  rate, n = rate x window, put in one order drawn from ``schedule_seed``.
  Every ``--seed`` gets the same arrivals: a tail over some tens of
  requests swings by tens of percent between random orders of one set of
  gaps, more than any bound could hold. The order is one whose p50 and
  p90 lie at the medians over 2,000 orders (``calibrate.py orders``);
* ``max_batch``: the engine's static batch;
* ``long_side`` and ``aspects`` ([w, h, share], ...): each image has
  this longer side and one of these aspect ratios; a run's images take
  the ratios in exactly these shares (largest remainder), in an order
  drawn from ``--seed``;
* ``pool_images`` (closed): distinct images made in set-up, served in
  turn.

Pixels are N(0, 1) from ``--seed``."""
from __future__ import annotations

import numpy as np


def _sizes(traffic: dict, n: int, rng) -> list:
    shares = np.asarray([a[2] for a in traffic["aspects"]], np.float64)
    exact = shares / shares.sum() * n
    count = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - count), kind="stable")[:n - count.sum()]:
        count[i] += 1
    side = int(traffic["long_side"])
    sizes = []
    for (aw, ah, _), c in zip(traffic["aspects"], count):
        w, h = (side, round(side * ah / aw)) if aw >= ah \
            else (round(side * aw / ah), side)
        sizes += [(int(h), int(w))] * int(c)
    return [sizes[i] for i in rng.permutation(len(sizes))]


def images(traffic: dict, n: int, seed: int) -> list:
    """n images (3, h, w) float32 for ``seed``."""
    rng = np.random.default_rng([int(seed), 1])
    return [rng.standard_normal((3, h, w), dtype=np.float32)
            for h, w in _sizes(traffic, n, rng)]


def open_schedule(traffic: dict, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps = gaps[np.random.default_rng(int(traffic["schedule_seed"]))
                .permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def n_images(traffic: dict, seconds: float) -> int:
    if traffic["loop"] == "open":
        return len(open_schedule(traffic, seconds))
    return int(traffic["pool_images"])


def sample(n_done: int, k: int, largest: int, seed: int) -> list:
    """Indices of ``k`` finished requests to check, drawn from ``seed``;
    ``largest`` (the request with the most pixels) is always among them."""
    rng = np.random.default_rng([int(seed), 2])
    rest = [i for i in rng.permutation(n_done) if i != largest]
    return [largest] + rest[:max(0, k - 1)]
