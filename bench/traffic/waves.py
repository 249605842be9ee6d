"""Closed-loop waves: ``clients`` requests sent together, the next wave once
the last of them has finished.

Every wave holds the same multiset of sizes, drawn once from the mix's
parameters, in an order that changes from wave to wave but not with the
seed: which request waits for a decode slot moves the tails, so two seeds
ask for the same work, and a run's spread is the system's own. The seed
draws the tokens, the documents and the requests checked afterwards.

* suffix lengths: the step grid ``min, min+step, .., max`` in equal shares,
  ``grid[floor((i + 1/2) * len(grid) / clients)]`` for client ``i``;
* output lengths: the log-uniform quantiles
  ``min * (max/min) ** ((i + 1/2) / clients)``, rounded;
* shared documents (where the mix has them): client counts in proportion to
  a Zipf law over the documents' popularity ranks, ``rank ** -zipf_s``,
  rounded by largest remainder; each request's prompt is its document
  followed by its own suffix.

Token ids are uniform over the vocabulary. Documents, every wave's tokens
and the warm-up wave draw from generators keyed by the seed and their own
tags; every wave's order from one keyed by the wave's index alone.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

#: each wave's requests take rids from wave * RID_STRIDE on
RID_STRIDE = 1 << 20
WARMUP_WAVE = -1
WARMUP_NEW_TOKENS = 8


@dataclasses.dataclass
class WaveRequest:
    rid: int
    prompt: List[int]
    max_new: int
    document: int = -1          # index of the shared document, -1 for none


def grid(spec: dict) -> List[int]:
    return list(range(spec["min"], spec["max"] + 1, spec["step"]))


def stratified(values: List[int], n: int) -> List[int]:
    return [values[int((i + 0.5) * len(values) / n)] for i in range(n)]


def log_uniform(lo: int, hi: int, n: int) -> List[int]:
    return [int(round(lo * (hi / lo) ** ((i + 0.5) / n))) for i in range(n)]


def zipf_counts(n_docs: int, s: float, n: int) -> List[int]:
    """Clients per document: n * rank**-s / sum, by largest remainder."""
    w = np.array([(r + 1) ** -s for r in range(n_docs)])
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


class Generator:
    def __init__(self, params: dict, seed: int, vocab: int):
        self.p = params
        self.seed = int(seed)
        self.vocab = vocab
        self.n = params["clients"]
        docs = params.get("documents")
        self.doc_tokens = docs["tokens"] if docs else 0
        rng = np.random.default_rng([self.seed, 0])
        self.documents = [rng.integers(0, vocab, self.doc_tokens).tolist()
                          for _ in range(docs["count"] if docs else 0)]
        self.suffix_lengths = stratified(grid(params["suffix_tokens"]), self.n)
        out = params["output_tokens"]
        self.output_lengths = log_uniform(out["min"], out["max"], self.n)
        self.doc_of_client = (
            [d for d, c in enumerate(zipf_counts(docs["count"],
                                                 docs["zipf_s"], self.n))
             for _ in range(c)] if docs else [-1] * self.n)

    @property
    def max_prompt(self) -> int:
        return self.doc_tokens + max(grid(self.p["suffix_tokens"]))

    @property
    def max_new(self) -> int:
        return self.p["output_tokens"]["max"]

    def _requests(self, tag: int, suffixes, outputs, docs) -> List[WaveRequest]:
        rng = np.random.default_rng([self.seed, 1, tag + 1])
        out = []
        for i, (s, o, d) in enumerate(zip(suffixes, outputs, docs)):
            prefix = self.documents[d] if d >= 0 else []
            suffix = rng.integers(0, self.vocab, s).tolist()
            out.append(WaveRequest((tag + 1) * RID_STRIDE + i,
                                   prefix + suffix, o, d))
        return out

    def wave(self, index: int) -> List[WaveRequest]:
        """Wave ``index`` (0, 1, ..): the fixed sizes in the wave's order."""
        rng = np.random.default_rng([2, index])
        return self._requests(index, rng.permutation(self.suffix_lengths),
                              rng.permutation(self.output_lengths),
                              rng.permutation(self.doc_of_client))

    def warmup(self) -> List[WaveRequest]:
        """Every suffix length of the grid once, each after a document where
        the mix has them, with a few output tokens: every prompt shape the
        waves send, and every decode scan width."""
        lengths = grid(self.p["suffix_tokens"])
        docs = [i % len(self.documents) if self.documents else -1
                for i in range(len(lengths))]
        return self._requests(WARMUP_WAVE, lengths,
                              [WARMUP_NEW_TOKENS] * len(lengths), docs)


def describe(gen: Generator) -> str:
    return (f"{gen.n} clients per wave; suffix lengths "
            f"{sorted(gen.suffix_lengths)}; output lengths "
            f"{sorted(gen.output_lengths)}; documents "
            f"{len(gen.documents)} x {gen.doc_tokens} tokens, clients per "
            f"document {[gen.doc_of_client.count(d) for d in range(len(gen.documents))]}"
            f"; mean output {math.fsum(gen.output_lengths) / gen.n:.1f}")
