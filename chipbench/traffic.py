"""The one traffic generator: closed loops of whole batches, from a mix's
parameters and the run's seed.

Every seed gets the same sizes, in another order, so that seeds change the
inputs and not the amount of work:

- prompt lengths: ``levels`` values spread evenly over ``prompt_len``
  (the midpoints of equal bins), served in pairs whose lengths add up to
  the same total, so that any even number of batches carries the same
  work.  Each cycle of ``levels`` batches shuffles the pairs and the order
  within each pair.  One batch has one prompt length: the engine pads a
  batch to its longest prompt and replays the padding, so ragged prompts
  in one batch would be served wrong;
- new tokens: the requests of a batch ask for ``requests_per_batch``
  values spread evenly over ``new_tokens`` (both ends included), shuffled;
- prompt token ids: uniform over the vocabulary it is given, the published
  one (the configuration's ``vocab_size``), never the padding rows of a
  program's table.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Batch:
    prompts: np.ndarray          # [requests, prompt_len] int32
    new_tokens: list[int]        # asked for, per request


def prompt_levels(mix: dict) -> list[int]:
    lo, hi = mix["prompt_len"]
    k = mix["levels"]
    if k % 2:
        raise ValueError("levels must be even: lengths are served in pairs")
    return [int(round(lo + (hi - lo) * (j + 0.5) / k)) for j in range(k)]


def new_token_levels(mix: dict) -> list[int]:
    lo, hi = mix["new_tokens"]
    b = mix["requests_per_batch"]
    return [int(round(lo + (hi - lo) * j / max(b - 1, 1))) for j in range(b)]


class Traffic:
    """``batch(i)`` is the i-th batch of the window; ``warm()`` one batch
    of the longest prompt, from a stream of its own."""

    def __init__(self, mix: dict, seed: int, vocab: int) -> None:
        self.mix, self.vocab = mix, vocab
        self._levels = prompt_levels(mix)
        self._news = new_token_levels(mix)
        self._rng = np.random.default_rng([seed, 0])
        self._warm_rng = np.random.default_rng([seed, 1])
        self._lengths: list[int] = []

    def _next_cycle(self) -> None:
        k = len(self._levels)
        pairs = [(self._levels[j], self._levels[k - 1 - j])
                 for j in range(k // 2)]
        for j in self._rng.permutation(len(pairs)):
            a, b = pairs[j]
            self._lengths += [a, b] if self._rng.random() < 0.5 else [b, a]

    def _make(self, rng, prompt_len: int) -> Batch:
        b = self.mix["requests_per_batch"]
        prompts = rng.integers(0, self.vocab, (b, prompt_len), dtype=np.int32)
        news = [self._news[j] for j in rng.permutation(b)]
        return Batch(prompts, news)

    def batch(self, i: int) -> Batch:
        while len(self._lengths) <= i:
            self._next_cycle()
        return self._make(self._rng, self._lengths[i])

    def warm(self) -> Batch:
        return self._make(self._warm_rng, max(self._levels))
