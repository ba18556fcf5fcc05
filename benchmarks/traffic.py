"""The one general traffic generator. A mix is a data file under
`benchmarks/traffic/`; this module turns its parameters and `--seed`
into requests or batches. A new mix is a new data file, not new code.

Every seed gets the same set of sizes in another order: lengths are a
fixed grid of quantiles of the mix's distributions, and the seed permutes
them and draws the token ids. So two seeds do the same work, as far as
a window reaches the whole pool. Where it reaches about half of it, and
the order decides what a cache still holds, the order is work too: such
a mix pins it ("order_seed"), and the seed draws the token ids alone.
"""

from __future__ import annotations

import statistics
import threading

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """n whole sizes at the quantiles (j + 0.5) / n of `dist`, clipped to
    [min, max]. Kinds: "lognormal" (median, sigma), "uniform"."""
    u = (np.arange(n) + 0.5) / n
    if dist["kind"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        raw = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    elif dist["kind"] == "uniform":
        raw = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown distribution kind {dist['kind']!r}")
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def zipf_counts(n_items: int, exponent: float, n: int) -> np.ndarray:
    """How often each of n_items ranks appears among n draws with weight
    1 / rank**exponent, by largest remainder: a fixed multiset."""
    w = 1.0 / np.arange(1, n_items + 1) ** exponent
    share = w / w.sum() * n
    counts = np.floor(share).astype(np.int64)
    for i in np.argsort(-(share - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


class RequestSource:
    """Request i of a serving mix: (prompt token ids, tokens to generate).
    `take()` hands out indices 0, 1, 2, ... to whichever caller asks next,
    so the i-th request sent is the same for a seed whatever the timing."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.vocab, self.seed = vocab, seed
        n = self.pool = int(mix["pool"])
        order = mix.get("order_seed", seed)
        self.prompt_len = _rng(order, 1).permutation(
            quantile_grid(mix["prompt"], n))
        self.answer_len = _rng(order, 2).permutation(
            quantile_grid(mix["answer"], n))
        shared = mix.get("shared")
        self.documents, self.doc_of = [], None
        if shared:
            self.documents = [
                _rng(seed, 3, k).integers(0, vocab, shared["tokens"]).tolist()
                for k in range(shared["documents"])]
            counts = zipf_counts(shared["documents"], shared["zipf"], n)
            self.doc_of = _rng(order, 4).permutation(
                np.repeat(np.arange(shared["documents"]), counts))
        self._next = 0
        self._open = True
        self._lock = threading.Lock()

    def get(self, i: int):
        j = i % self.pool
        own = _rng(self.seed, 5, i).integers(
            0, self.vocab, int(self.prompt_len[j])).tolist()
        if self.doc_of is not None:
            own = self.documents[int(self.doc_of[j])] + own
        return own, int(self.answer_len[j])

    def take(self):
        with self._lock:
            if not self._open:
                return None
            i = self._next
            self._next += 1
            return i

    def close(self) -> None:
        with self._lock:
            self._open = False


def lm_batch(mix: dict, vocab: int, seed: int, step: int):
    """Batch `step` of a training mix: (inputs, targets), int32
    [batch, seq]. A learnable stream: next token = token + stride mod
    vocab, from a start drawn per row and per step, so all rows differ."""
    b, t = mix["batch"], mix["seq"]
    start = _rng(seed, 6, step).integers(0, vocab, (b, 1))
    tok = ((start + mix["stride"] * np.arange(t + 1)[None, :])
           % vocab).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]
