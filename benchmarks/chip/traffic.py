"""The one traffic generator: reads a mix's parameters, makes its requests.

Prompts are SQuAD-like topic-cluster token streams (a copy of the
program's ``data.pipeline.PromptWorkload``, kept here so that no change to
the program can move the yardstick): each cluster biases tokens to a band
of the vocabulary, so MoE routers see structured, repeatable expert
preferences.

Lengths come from a heavy-tailed (log-normal) distribution clipped to
``[min, max]`` and rounded to ``quantum`` tokens. Every seed uses the same
multiset of lengths, the ``distinct`` quantiles of that distribution, dealt
to the clients in strata: with 4 clients and 16 lengths, clients 0, 1, 2,
3 hold quantiles 0, 4, 8, 12 / 1, 5, 9, 13 / ..., so each spans short to
long, and client c cycles through its stratum starting at its c-th length.
The dealing is the same for every seed, and the seed picks every token:
which client is served first decides, under round-robin prefill, how the
passes interleave, so a seed that moved lengths between clients moved the
time to first token by a quarter. The prompt lengths a run can meet are
the multiples of ``quantum``, which the harness warms up.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Tuple

import numpy as np

_SEED_MASK = (1 << 64) - 1


def _rng(*key: int) -> np.random.Generator:
    """A generator for any whole-number seed (negative or past 64 bits
    included)."""
    return np.random.default_rng([int(k) & _SEED_MASK for k in key])


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    vocab: int
    n_clusters: int
    prompt_len: Tuple[int, int]   # (min, max)
    zipf_a: float = 1.3


def squad_like(vocab: int) -> DatasetSpec:
    return DatasetSpec("squad", vocab, n_clusters=12, prompt_len=(32, 128))


class PromptWorkload:
    """Serving workload: prompts drawn from topic clusters (copied from
    ``repro.data.pipeline``, where ``prompt`` draws the length too; here
    ``prompt_of`` takes the length and the caller's generator)."""

    def __init__(self, spec: DatasetSpec, seed: int = 0):
        self.spec = spec
        self.rng = _rng(seed)
        self.centers = self.rng.integers(
            0, spec.vocab, size=(spec.n_clusters,))
        self.band = max(spec.vocab // (2 * spec.n_clusters), 16)

    def prompt_of(self, n: int, rng: np.random.Generator) -> np.ndarray:
        c = int(rng.integers(0, self.spec.n_clusters))
        toks = self.centers[c] + rng.integers(-self.band, self.band, size=n)
        return np.mod(toks, self.spec.vocab).astype(np.int32)


def lengths(d: dict) -> List[int]:
    """The fixed multiset of ``d["distinct"]`` lengths: quantiles of a
    log-normal with ``median`` and ``sigma``, clipped and rounded."""
    nd = statistics.NormalDist()
    lo, hi, q = d["min"], d["max"], d.get("quantum", 1)
    out = []
    for i in range(d["distinct"]):
        z = nd.inv_cdf((i + 0.5) / d["distinct"])
        x = d["median"] * float(np.exp(d["sigma"] * z))
        x = int(round(x / q)) * q
        out.append(int(min(max(x, lo), hi)))
    return out


@dataclasses.dataclass
class Request:
    client: int
    index: int
    prompt: np.ndarray
    out_tokens: int   # tokens the request generates, first included


class Traffic:
    """Per-client request streams of one mix for one seed. Client ``c``'s
    ``i``-th request is the same on every call with the same seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.seed = int(seed)
        self.workload = PromptWorkload(squad_like(vocab), seed=self.seed)
        self.prompt_lengths = lengths(mix["prompt_tokens"])
        self.out_lengths = lengths(mix["output_tokens"])

    @property
    def clients(self) -> int:
        return int(self.mix["clients"])

    def _dealt(self, lengths: List[int], client: int, index: int) -> int:
        """Client ``client``'s ``index``-th length from its stratum: the
        same for every seed."""
        k = self.clients
        stratum = sorted(lengths)[client::k]
        return stratum[(client + index) % len(stratum)]

    def request(self, client: int, index: int) -> Request:
        n = self._dealt(self.prompt_lengths, client, index)
        out = self._dealt(self.out_lengths, client, index)
        prompt = self.workload.prompt_of(n, _rng(self.seed, client, index, 2))
        return Request(client, index, prompt, out)


def fixed_prompt(vocab: int, n: int, key: int) -> np.ndarray:
    """A topic-cluster prompt of ``n`` tokens that depends on ``key``
    alone (warm-up and predictor traces: the same in every run)."""
    wl = PromptWorkload(squad_like(vocab), seed=key)
    return wl.prompt_of(n, _rng(key, n))
