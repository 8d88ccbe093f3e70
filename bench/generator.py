"""The one traffic generator: reads a mix's parameters (``traffic/<mix>.json``)
and yields, for token ``k``, the item ids that token requests.

A token's ids depend on ``(seed, k)`` alone, so the same seed gives the
same stream however fast the system runs.  Item ids lie in
``[0, n_items)``; the system maps them to what it serves (element indices
of an array).

Parameters of a mix:

``loop``           ``"closed"``: ``in_flight`` tokens are outstanding, and a
                   token is submitted only when one completes.
``in_flight``      tokens outstanding at once.
``lanes``          item ids per token.
``pick``           ``"uniform"``: ids drawn uniformly from ``[0, n_items)``.
``warmup_tokens``  tokens run in set-up, before the window, from the same
                   stream (the window starts at token ``warmup_tokens``).
"""
from __future__ import annotations

import numpy as np

PICKS = ("uniform",)


class Traffic:
    """The token stream of one mix under one seed over ``n_items`` items."""

    def __init__(self, mix: dict, seed: int, n_items: int):
        self.mix, self.seed, self.n_items = mix, int(seed), int(n_items)
        if mix.get("loop", "closed") != "closed":
            raise ValueError(f"unknown loop {mix.get('loop')!r}")
        self.lanes = int(mix["lanes"])
        self.in_flight = int(mix["in_flight"])
        self.warmup_tokens = int(mix.get("warmup_tokens", self.in_flight))
        self.pick = mix["pick"]
        if self.pick not in PICKS:
            raise ValueError(f"unknown pick {self.pick!r}")

    def token(self, k: int) -> np.ndarray:
        """Item ids of token ``k`` (int64, ``lanes`` long)."""
        rng = np.random.default_rng([self.seed % (1 << 64), k])
        return rng.integers(0, self.n_items, self.lanes, dtype=np.int64)
