"""Whether what the timed path served is correct: a sample of the requests
the window finished, each replayed through the plain reference the
configuration names (``bench/references/``) over its prompt and its served
tokens, row by row under the swap triples it was served under.  Per
served token, the gap is the reference's best logit at that position less
its logit of the served token (greedy serving puts the best first); the
configuration names the statistic of the gaps that is compared
(``correct.statistic``) and its limit."""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

import numpy as np

from . import reference

SAMPLE = 8                # requests replayed per run: the longest + 7 drawn

# the numbers a configuration may compare (``correct.statistic``), each a
# function of one replay's per-token ``gap`` and ``rank`` arrays
STATISTICS = {
    "gap_max": lambda r: float(r["gap"].max()),
    "gap_mean": lambda r: float(r["gap"].mean()),
    "rank_p90": lambda r: float(np.percentile(r["rank"], 90)),
}


def summary(r: dict, prefix: str = "") -> dict:
    """Every statistic of one replay (``prefix`` picks the control's)."""
    if not r[prefix + "gap"].size:
        return {}
    sub = dict(gap=r[prefix + "gap"], rank=r[prefix + "rank"])
    out = {k: f(sub) for k, f in STATISTICS.items()}
    out.update(tokens=int(sub["gap"].size),
               not_first=float((sub["rank"] > 1).mean()))
    return out


def sample(finished: list, seed: int, inside: Optional[set] = None) -> list:
    """The longest finished request and ``SAMPLE - 1`` others drawn from
    ``seed`` (some hundreds of served tokens).  ``inside``: the rids
    retired in the window, preferred when there are enough."""
    pool = [c for c in finished if inside is None or c.rid in inside]
    if len(pool) < SAMPLE:
        pool = list(finished)
    if not pool:
        return []
    pool.sort(key=lambda c: c.rid)
    longest = max(pool, key=lambda c: (len(c.tokens), -c.rid))
    rest = [c for c in pool if c is not longest]
    order = np.random.default_rng([int(seed), 11]).permutation(len(rest))
    return [longest] + [rest[int(i)] for i in order[:SAMPLE - 1]]


def padded_length(mix: dict, block: int) -> int:
    """One replay length for the whole cell (one compile): the longest
    prompt plus served tokens, rounded up to the reference's row block."""
    n = max(mix["prompt_buckets"]) + mix["new_token_bucket"] - 1
    return -(-n // block) * block


def rows(prompt: np.ndarray, served: np.ndarray, first_step: int,
         by_step: Dict[int, dict], prefill_triple, targets, length: int):
    """Tokens, next tokens and per-target row triples of one replay.  Row
    ``r`` holds token ``r`` of prompt + served[:-1]; its logits should put
    ``nxt[r]`` first from row ``len(prompt) - 1`` on.  Prompt rows ran in
    the prefill, under ``prefill_triple``; the row of served token ``i``
    ran in decode step ``first_step + i``."""
    L, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    S = len(seq)
    if S > length:
        raise ValueError(f"replay of {S} rows > padded length {length}")
    tokens = np.zeros(length, np.int32)
    tokens[:S] = seq
    nxt = np.zeros(length, np.int32)
    nxt[:S] = np.concatenate([prompt[1:], served])
    triples = {}
    for t in targets:
        tr = np.tile(np.asarray(prefill_triple, np.int32), (length, 1))
        for i in range(n - 1):
            tr[L + i] = by_step[first_step + i][t]
        triples[t] = tr
    return tokens, nxt, triples, slice(L - 1, L - 1 + n)


def first_step(step_start: Dict[int, float], splice_t: float) -> int:
    order = sorted(step_start)
    i = bisect.bisect_right([step_start[k] for k in order], splice_t)
    if i >= len(order):
        raise ValueError("no decode step after the request's splice")
    return order[i]


def replay(ref, picked: list, prompts: dict, first_steps: dict,
           by_step: dict, prefill_triple, targets, length: int,
           control: bool = False) -> dict:
    """Gap and rank of every served token of ``picked`` (and of the
    control's first choice at the same rows, with ``control``), each
    replayed through ``ref`` (a reference module's ``Reference``) at
    ``length`` rows."""
    keys = ("gap", "rank") + (("control_gap", "control_rank") if control else ())
    got: Dict[str, List[np.ndarray]] = {k: [] for k in keys}
    for c in picked:
        served = np.asarray(c.tokens, np.int32)
        tokens, nxt, triples, sl = rows(
            prompts[c.rid], served, first_steps[c.rid], by_step,
            prefill_triple, targets, length)
        out = reference.gaps(ref, tokens, nxt, triples, control=control)
        for k in keys:
            got[k].append(out[k][sl])
    return {k: np.concatenate(v) if v else np.zeros(0) for k, v in got.items()}
