"""The one traffic generator: a traffic file's parameters plus a seed in,
documents and requests out.

Adapted from the program's ``repro.serving.traffic`` (seeded Poisson
arrivals, per-tenant document reuse), with what a chip benchmark needs on
top: lengths drawn from log-normal distributions, documents of whole
128-token blocks with Zipf popularity, prompts unique from their first
token, and due times for an open loop.

Every seed serves the same work on the same schedule.  The sizes
(document of each request, question and output lengths, arrival gaps) are
a fixed stratified set: quantiles of the stated distributions.  Their
order is one fixed permutation, the same for all seeds, so an open loop
offers the same requests at the same times in every run; a window holds
too few requests for the order to average out, and a seed that reordered
them would change how they queue.  The seed writes the text: documents,
questions, and so the tokens served.  An open loop gets exactly
``rate * seconds`` arrivals spread over the window; a closed loop draws
rounds, each a permutation of one stratified set, so every multiple of a
round's length holds the same sizes.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass

import numpy as np

BLOCK = 128
BOS, BYTE0 = 1, 3       # the byte tokenizer's leading token and first byte id
ALPHABET = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 .,",
    np.uint8)


@dataclass(frozen=True)
class Spec:
    """One request: its prompt text, lengths in tokens, and due time."""

    index: int
    prompt: str
    doc: int | None          # document index, None when unshared
    prompt_tokens: int       # with the tokenizer's leading BOS
    max_new_tokens: int
    due_s: float | None      # open loop: seconds after the window opens


def _quantiles(dist: dict, n: int) -> list[int]:
    """``n`` stratified draws of a clipped log-normal, in ascending order."""
    nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
    return [int(min(dist["max"], max(dist["min"],
                                     round(math.exp(nd.inv_cdf((i + 0.5) / n))))))
            for i in range(n)]


def _zipf_counts(weights: list[float], n: int) -> list[int]:
    """Integer counts proportional to ``weights`` summing to ``n``
    (largest remainder)."""
    total = sum(weights)
    raw = [w * n / total for w in weights]
    counts = [int(r) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])[
            :n - sum(counts)]:
        counts[i] += 1
    return counts


def doc_blocks(traffic: dict) -> list[int]:
    """Blocks of each document, by popularity rank (fixed for all seeds):
    evenly spaced sizes over the stated range, ranked in an interleaved
    order so that the popular documents are not all long or all short."""
    d = traffic["documents"]
    lo, hi = d["blocks"]
    n = d["count"]
    sizes = [round(lo + j * (hi - lo) / max(n - 1, 1)) for j in range(n)]
    order = [sizes[(j * 3) % n] for j in range(n)] if n % 3 else sizes
    return order


def size_set(traffic: dict, n: int) -> list[tuple[int, int, int]]:
    """The stratified set of ``n`` requests: (document rank, question
    tokens, output tokens).  Lengths are paired by a fixed permutation so
    that long questions do not all come with long outputs."""
    d = traffic["documents"]
    weights = [1.0 / (r + 1) ** d["zipf"] for r in range(d["count"])]
    docs = [r for r, c in enumerate(_zipf_counts(weights, n))
            for _ in range(c)]
    q = _quantiles(traffic["question_tokens"], n)
    out = _quantiles(traffic["output_tokens"], n)
    fixed = np.random.default_rng(0)
    q = [q[i] for i in fixed.permutation(n)]
    out = [out[i] for i in fixed.permutation(n)]
    return list(zip(docs, q, out))


def tokens(text: str) -> list[int]:
    """Token ids of a prompt: BOS, then one id per byte (vocabularies of
    more than 259 entries, as every configuration here has)."""
    return [BOS] + [BYTE0 + b for b in text.encode("utf-8")]


class Traffic:
    """Documents and requests for one run of one traffic file."""

    def __init__(self, traffic: dict, seed: int) -> None:
        self.cfg = traffic
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 0x7AFF1C]))
        # the schedule's order: one fixed draw for every seed
        self.order = np.random.default_rng(np.random.SeedSequence(0x0DE5))
        self.blocks = doc_blocks(traffic)
        self.unique = bool(traffic.get("unique", False))
        # a document's text with the BOS fills whole blocks exactly
        self.documents = [self.text(b * BLOCK - 1) for b in self.blocks]
        self.round = (traffic["clients"] * traffic.get("rounds_per_client", 2)
                      if traffic["loop"] == "closed" else None)
        self._drawn = 0

    def text(self, n: int) -> str:
        return ALPHABET[self.rng.integers(0, len(ALPHABET), n)].tobytes(
        ).decode("ascii")

    def _spec(self, index: int, size, due_s) -> Spec:
        rank, q, out = size
        doc = self.text(self.blocks[rank] * BLOCK - 1) if self.unique \
            else self.documents[rank]
        prompt = doc + self.text(q)
        return Spec(index=index, prompt=prompt,
                    doc=None if self.unique else rank,
                    prompt_tokens=1 + len(prompt), max_new_tokens=out,
                    due_s=due_s)

    def open_loop(self, seconds: float) -> list[Spec]:
        """``rate * seconds`` arrivals due over ``[0, seconds)``: the
        gaps are stratified exponential quantiles, shuffled, and scaled so
        that they sum to the window."""
        n = max(1, round(self.cfg["rate_rps"] * seconds))
        sizes = size_set(self.cfg, n)
        sizes = [sizes[i] for i in self.order.permutation(n)]
        gaps = np.asarray([-math.log(1 - (i + 0.5) / n) for i in range(n)])
        gaps = gaps[self.order.permutation(n)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        due *= seconds / gaps.sum()
        return [self._spec(i, s, float(t))
                for i, (s, t) in enumerate(zip(sizes, due))]

    def next_closed(self) -> Spec:
        """The next request of a closed loop (rounds of one stratified
        set, each in a fresh order)."""
        if self._drawn % self.round == 0:
            sizes = size_set(self.cfg, self.round)
            self._round = [sizes[i] for i in
                           self.order.permutation(self.round)]
        spec = self._spec(self._drawn, self._round[self._drawn % self.round],
                          None)
        self._drawn += 1
        return spec

    def max_prompt_tokens(self) -> int:
        return max(self.blocks) * BLOCK + self.cfg["question_tokens"]["max"]
