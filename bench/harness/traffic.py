"""The one traffic generator: reads a mix file's parameters and draws a
request plan from ``--seed``.

Every seed serves the same sizes and gaps in another order.  Lengths and
inter-arrival gaps come from a pool of ``POOL`` quantiles of their
distribution; each consecutive block of ``POOL`` requests takes every pool
entry once, in an order drawn from ``--seed``.  So any two runs that serve
a few blocks do the same work, and a seed changes only which request is
where.  The prompt tokens are drawn from ``--seed`` itself."""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

POOL = 64                # quantiles per pool = requests per block
_SAMPLE = 1 << 20        # draws behind the quantiles (fixed seed)
_POOL_SEED = 20240709

ARRIVAL_KINDS = ("poisson", "gamma", "backlog")


@dataclasses.dataclass
class Planned:
    """One request of the plan: its index, when it is due (seconds from the
    start of serving; None in a backlog, where a request is due when it is
    queued), its prompt tokens and its output budget."""
    idx: int
    due: Optional[float]
    tokens: np.ndarray
    max_new: int


def _quantiles(draws: np.ndarray) -> np.ndarray:
    return np.quantile(draws, (np.arange(POOL) + 0.5) / POOL)


def lengths(spec: dict) -> np.ndarray:
    """The pool of lengths: quantiles of the clipped distribution."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.random.default_rng(_POOL_SEED).standard_normal(_SAMPLE)
    x = _quantiles(np.exp(np.log(spec["median"]) + spec["sigma"] * z))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(spec: dict) -> np.ndarray:
    """The pool of inter-arrival gaps, scaled to a mean of 1/rate."""
    rng = np.random.default_rng(_POOL_SEED)
    if spec["kind"] == "poisson":
        g = _quantiles(rng.exponential(1.0, _SAMPLE))
    elif spec["kind"] == "gamma":
        shape = 1.0 / spec["cv"] ** 2           # CV of Gamma(k, .) is 1/sqrt(k)
        g = _quantiles(rng.gamma(shape, 1.0, _SAMPLE))
    else:
        raise ValueError(f"no gaps for arrivals {spec['kind']!r}")
    return g / g.mean() / spec["rate_rps"]


def check(mix: dict) -> None:
    """Refuse a mix whose lengths do not fit its buckets."""
    p, o = mix["prompt_len"], mix["output_len"]
    if p["min"] < 1 or p["max"] > max(mix["prompt_buckets"]):
        raise ValueError(f"mix {mix['name']}: prompt lengths {p['min']}.."
                         f"{p['max']} outside 1..{max(mix['prompt_buckets'])}")
    if o["min"] < 1 or o["max"] > mix["new_token_bucket"]:
        raise ValueError(f"mix {mix['name']}: output lengths {o['min']}.."
                         f"{o['max']} outside 1..{mix['new_token_bucket']}")
    if mix["arrivals"]["kind"] not in ARRIVAL_KINDS:
        raise ValueError(f"mix {mix['name']}: arrivals "
                         f"{mix['arrivals']['kind']!r}")


class Plan:
    """An endless request plan for one run (see the module docstring)."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        check(mix)
        self.prompt_lens = lengths(mix["prompt_len"])
        self.output_lens = lengths(mix["output_len"])
        self.backlog = mix["arrivals"]["kind"] == "backlog"
        self.gaps = None if self.backlog else gaps(mix["arrivals"])
        self.seed = int(seed)
        self.vocab = int(vocab)
        self._perms = {}
        self._due = 0.0
        self._next = 0

    def _pick(self, pool: np.ndarray, salt: int, i: int):
        key = (salt, i // POOL)
        if key not in self._perms:
            self._perms[key] = np.random.default_rng(
                [self.seed, salt, i // POOL]).permutation(POOL)
        return pool[self._perms[key][i % POOL]]

    def request(self, i: int, due: Optional[float] = None) -> Planned:
        L = int(self._pick(self.prompt_lens, 1, i))
        toks = np.random.default_rng([self.seed, 4, i]).integers(
            0, self.vocab, L).astype(np.int32)
        return Planned(i, due, toks, int(self._pick(self.output_lens, 2, i)))

    def next_due(self) -> Optional[float]:
        """Due time of the next open-loop request (None in a backlog)."""
        if self.backlog:
            return None
        return self._due + float(self._pick(self.gaps, 3, self._next))

    def take(self) -> Planned:
        """The next request of the plan, stamped with its due time."""
        due = None if self.backlog else self.next_due()
        if due is not None:
            self._due = due
        self._next += 1
        return self.request(self._next - 1, due)

    def take_due(self, now: float) -> List[Planned]:
        """Every open-loop request due by ``now`` (seconds)."""
        out = []
        while not self.backlog and self.next_due() <= now:
            out.append(self.take())
        return out
