"""Seeded open-loop traffic: one general generator that reads a mix file.

A mix (``bench/traffic/<name>.json``) fixes the sizes and the arrival shape;
``--seed`` fixes the content and, unless the mix's ``order`` is ``fixed``, the
order. The multiset of sizes and gaps is drawn from the mix's own
``master_seed``, so every seed serves the same work (the same prompt lengths,
output lengths, popularity ranks and arrival gaps) in another order and with
other words; with ``"order": "fixed"`` in the same order, so that a cell whose
latency depends on how its long requests queue (a backend serving one
generation at a time) does not change its queue from seed to seed.

The popularity and burst arithmetic follows ``repro.gateway.traffic``
(Zipf weights ``(rank + 1) ** -s``; ON/OFF bursts entered with a fixed
probability, inside which arrivals come ``burst_rate_factor`` times faster).
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))
# the words of every prompt share one seeded vocabulary, so prompts overlap
# in words the way a real application's questions do
VOCAB_WORDS = 4096


@dataclass
class Request:
    t_due: float  # seconds after the window opens
    prompt: str
    kind: str  # "repeat" (a cached prompt) or "novel"
    max_tokens: int
    rank: int = -1  # popularity rank of the cached prompt repeated, -1 if novel


@dataclass
class Workload:
    """Everything one run needs from the mix: the cached corpus that set-up
    inserts, and the timed schedule (warm-up stream first, then window)."""

    corpus: List[str]  # cached prompts by popularity rank
    answers: List[str]  # their cached answers
    warmup: List[Request]
    window: List[Request]
    meta: Dict = field(default_factory=dict)


def load_mix(path: Path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("arrivals", "cached_prompts", "zipf_s", "repeat_share",
                "prompt_words", "max_tokens", "answer_words", "master_seed",
                "warmup_seconds"):
        if key not in mix:
            raise ValueError(f"{path}: mix has no {key!r}")
    return mix


def _lognormal_sizes(rng, spec: dict, n: int) -> np.ndarray:
    """n integer sizes: lognormal around ``median`` with ``sigma``, clipped."""
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(master, mix: dict, n: int, rate: float) -> List[List[float]]:
    """Arrival gaps grouped into episodes (a burst, or a single arrival).
    Episodes are what a seed reorders, so a burst stays a burst."""
    arr = mix["arrivals"]
    mean = 1.0 / rate
    if arr["kind"] == "poisson":
        return [[g] for g in master.exponential(mean, n)]
    if arr["kind"] != "onoff":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    episodes: List[List[float]] = []
    left = n
    while left > 0:
        if master.random() < arr["burst_prob"]:
            k = min(int(arr["burst_len"]), left)
            ep = list(master.exponential(mean / arr["burst_rate_factor"], k - 1))
            ep.append(float(master.exponential(mean)))
        else:
            k, ep = 1, [float(master.exponential(mean))]
        episodes.append(ep)
        left -= k
    return episodes


def _words(rng, vocab: np.ndarray, n: int) -> str:
    return " ".join(vocab[rng.integers(len(vocab), size=n)])


def _vocab(rng) -> np.ndarray:
    lens = rng.integers(3, 10, size=VOCAB_WORDS)
    words = {"".join(rng.choice(LETTERS, int(k))) for k in lens}
    return np.array(sorted(words))


def _stream(mix, master, rng, vocab, corpus, seconds: float, rate: float,
            tag: str) -> List[Request]:
    """One timed stream of ``rate * seconds`` requests over ``seconds``."""
    n = max(1, int(round(rate * seconds)))
    # sizes and kinds: fixed multisets from the master stream
    n_rep = int(round(mix["repeat_share"] * n))
    kinds = np.array(["repeat"] * n_rep + ["novel"] * (n - n_rep))
    w = (np.arange(mix["cached_prompts"]) + 1.0) ** -mix["zipf_s"]
    ranks = master.choice(mix["cached_prompts"], size=n_rep, p=w / w.sum())
    novel_len = _lognormal_sizes(master, mix["prompt_words"], n - n_rep)
    max_tok = _lognormal_sizes(master, mix["max_tokens"], n)
    episodes = _gaps(master, mix, n, rate)
    # order and content: from the run's seed; a mix whose "order" is "fixed"
    # keeps the master stream's order, so that every seed queues the same
    # work at the same times and only the content differs
    if mix.get("order", "seeded") == "seeded":
        kinds = kinds[rng.permutation(n)]
        ranks = ranks[rng.permutation(n_rep)]
        novel_len = novel_len[rng.permutation(len(novel_len))]
        max_tok = max_tok[rng.permutation(n)]
        episodes = [episodes[i] for i in rng.permutation(len(episodes))]
    else:
        # the kinds interleave evenly (a master-seeded shuffle), not in two runs
        kinds = kinds[master.permutation(n)]
    gaps = np.array([g for ep in episodes for g in ep][:n])
    # open loop over exactly `seconds`: the last gap ends at the window close
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])]) * (seconds / gaps.sum())
    out: List[Request] = []
    ri = ni = 0
    for i in range(n):
        if kinds[i] == "repeat":
            r = int(ranks[ri])
            ri += 1
            out.append(Request(float(t[i]), corpus[r], "repeat", int(max_tok[i]), r))
        else:
            # a question nobody asked before; the tag keeps it unique
            words = _words(rng, vocab, int(novel_len[ni]) - 1)
            ni += 1
            out.append(Request(float(t[i]), f"{words} {tag}{i}", "novel",
                               int(max_tok[i])))
    return out


def make_workload(mix: dict, seed: int, seconds: float,
                  rate: Optional[float] = None) -> Workload:
    """The run's inputs from (mix, seed, seconds). Same arguments, same bytes."""
    if rate is None and "rate" not in mix:
        raise ValueError("the mix states no rate: sweep its knee on the chip "
                         "(--rate r1,r2,...) and give it 4/5 of the knee")
    rate = float(mix["rate"] if rate is None else rate)
    master = np.random.default_rng(mix["master_seed"])
    rng = np.random.default_rng([seed, 0x5EED])
    vocab = _vocab(rng)
    n_c = mix["cached_prompts"]
    plen = _lognormal_sizes(master, mix["prompt_words"], n_c)
    # the least popular prompts carry the longest and shortest lengths, so
    # every length bucket a request can fall in has a cached prompt for
    # set-up to warm it with
    p = mix["prompt_words"]
    edge = [p["min"], 15, 31, 63, p["max"]]
    edge = [e for e in edge if p["min"] <= e <= p["max"]]
    plen[n_c - len(edge):] = edge
    alen = _lognormal_sizes(master, mix["answer_words"], n_c)
    corpus, answers = [], []
    for r in range(n_c):
        # rank tag keeps every cached prompt distinct
        corpus.append(f"{_words(rng, vocab, int(plen[r]) - 1)} q{r}")
        answers.append(f"A{r}: {_words(rng, vocab, int(alen[r]))}")
    warm = _stream(mix, master, rng, vocab, corpus, mix["warmup_seconds"], rate, "w")
    window = _stream(mix, master, rng, vocab, corpus, seconds, rate, "n")
    return Workload(corpus, answers, warm, window,
                    {"rate": rate, "seconds": seconds, "seed": seed})
