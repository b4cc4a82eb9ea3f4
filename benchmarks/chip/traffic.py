"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``traffic/<name>.json``) sets:

* ``arrivals``: ``"poisson"`` (an open loop at ``rate_per_s``, paced by
  ``block_s``: every block of that many seconds holds the same number of
  arrivals) or ``"backlog"`` (an offline job: ``backlog_batches`` full
  batches are kept queued ahead of the engine);
* ``prompt``: ``{"dist": "fixed", "tokens": n}``, with an optional
  ``template_tokens``: a prefix shared by every prompt;
* ``gen_tokens`` and ``batch_size``, which the engine is run with.

Every seed gets the same work in another order. Gaps between arrivals are
the quantiles of the exponential at ``(k + 1/2) / n``, shuffled by the seed;
so each seed sends the same number of requests in the window, of the same
sizes, and seeds differ only in order and in token ids. Token ids are drawn
from ``[2, vocab)``; 0 and 1 are left out because the engine pads with 1.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

FIRST_ID = 2


@dataclass
class Request:
    id: str
    prompt: List[int]
    due: float          # seconds after the window opens; 0 for a backlog


class Traffic:
    def __init__(self, mix: Dict, seed: int, vocab: int) -> None:
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.batch_size = int(mix["batch_size"])
        self.gen_tokens = int(mix["gen_tokens"])
        self.open_loop = mix["arrivals"] == "poisson"
        if not self.open_loop and mix["arrivals"] != "backlog":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        if mix["prompt"]["dist"] != "fixed":
            raise ValueError(f"unknown prompt dist {mix['prompt']['dist']!r}")
        self.prompt_tokens = int(mix["prompt"]["tokens"])
        rng = np.random.default_rng([seed, 0])
        n = int(mix.get("template_tokens", 0))
        self.template = rng.integers(FIRST_ID, vocab, n).tolist()

    def _rng(self, stream: int, i: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, stream, i])

    def prompt(self, i: int, length: int = 0) -> List[int]:
        length = length or self.prompt_tokens
        own = length - len(self.template)
        if own < 1:
            raise ValueError(f"prompt of {length} tokens is shorter than the "
                             f"{len(self.template)}-token template")
        return self.template + self._rng(2, i).integers(
            FIRST_ID, self.vocab, own).tolist()

    def window(self, seconds: float) -> List[Request]:
        """The open loop's requests, due inside ``[0, seconds)``: the window
        is cut into blocks of ``block_s`` seconds, each with ``rate *
        block_s`` arrivals spread over it by shuffled gaps."""
        rate = float(self.mix["rate_per_s"])
        block = float(self.mix["block_s"])
        due: List[float] = []
        start = 0.0
        while start < seconds:
            span = min(block, seconds - start)
            n = max(1, int(rate * span))
            gaps = [-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)]
            self._rng(3, len(due)).shuffle(gaps)
            due += list(start + (np.cumsum(gaps) - gaps) * (span / sum(gaps)))
            start += span
        return [Request(f"r{i}", self.prompt(i), float(d))
                for i, d in enumerate(due)]

    def backlog(self, start: int, n: int) -> List[Request]:
        """Requests ``start .. start+n-1`` of an offline job."""
        return [Request(f"r{i}", self.prompt(i), 0.0)
                for i in range(start, start + n)]
