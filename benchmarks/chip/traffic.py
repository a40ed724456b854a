"""One general generator for every traffic mix: a mix is a data file of
parameters under ``traffic/``, and this module turns it and a seed into the
workload, its query texts and the client's schedule.

Every seed gets the same *set* of sizes in another order: query lengths,
answer lengths and the open loop's inter-arrival gaps are stratified quantiles
of their distributions (``(i + 0.5) / n``), permuted by the seed.  So two seeds
load the system alike, and the seed changes which text goes where, not how much
work there is.  The shape parameters (the ``task`` block of a mix) are copied
from the program's ``BENCHMARKS`` table, so the yardstick does not move when
the program does.
"""
from __future__ import annotations

import string
from dataclasses import dataclass

import numpy as np
from scipy import stats

QUERY_PREFIX_BYTES = 4          # "\nQ1:" ahead of every query in a batch prompt
SPLITS = {"train": 512, "val": 64}   # queries the modeling stage is fitted and tuned on
EMBED_DIM = 64                  # width of the query embeddings the router reads
CLOSED_MAX_QPS = 100.0          # a closed loop never completes more: room for later speed-ups
_ALPHABET = np.frombuffer((string.ascii_lowercase + "      ").encode(), np.uint8)


@dataclass
class Traffic:
    """What the harness builds from one mix and one seed."""

    system_prompt: str
    queries: list[str]              # by workload index
    answers: list[str]
    in_tokens: np.ndarray           # (n,) tokens of "\nQi:<query>", as billed
    out_tokens: np.ndarray          # (n,) expected answer tokens (cost model)
    difficulty: np.ndarray
    topic: np.ndarray
    embeddings: np.ndarray
    split: dict                     # name -> workload indices
    order: np.ndarray               # test-split indices in the order clients send them
    due_s: np.ndarray               # open loop: send offsets in the window; closed: empty


def rng_for(seed: int, salt: str) -> np.random.Generator:
    """A numpy generator per (seed, purpose); any non-negative seed width."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt.encode()]))


def stratified(ppf, n: int, rng: np.random.Generator) -> np.ndarray:
    """The n stratified quantiles of a distribution, in a seeded order."""
    return rng.permutation(ppf((np.arange(n) + 0.5) / n))


def lognormal_ppf(median: float, sigma: float):
    return lambda u: stats.lognorm.ppf(u, s=sigma, scale=median)


def open_loop_due(rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Send offsets of ``round(rate * seconds)`` requests in ``[0, seconds)``:
    Poisson-like gaps (stratified exponential quantiles in a seeded order),
    scaled so that every seed sends the same number in the same time."""
    n = max(1, int(round(rate * seconds)))
    gaps = stratified(lambda u: stats.expon.ppf(u, scale=1.0 / rate), n, rng)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])]) * (seconds / gaps.sum())


def random_text(n_bytes: int, rng: np.random.Generator) -> str:
    """ASCII lowercase words of exactly ``n_bytes`` bytes, no edge spaces."""
    chars = _ALPHABET[rng.integers(0, len(_ALPHABET), n_bytes)]
    chars[0] = chars[-1] = ord("q")
    return chars.tobytes().decode()


def n_queries_needed(mix: dict, seconds: float) -> int:
    """Test-split size that a run of ``seconds`` can never exhaust: an open
    loop sends ``rate * seconds`` queries; a closed loop at most what its
    clients can finish at ``CLOSED_MAX_QPS``."""
    load = mix["load"]
    rate = load["rate_qps"] if load["loop"] == "open" else CLOSED_MAX_QPS
    return int(np.ceil(rate * seconds * 1.5)) + 64


def generate(mix: dict, seed: int, seconds: float) -> Traffic:
    task = mix["task"]
    splits = SPLITS
    n_test = n_queries_needed(mix, seconds)
    n = splits["train"] + splits["val"] + n_test
    rng = rng_for(seed, "traffic")

    q_med, q_sigma = task["query_tokens"]
    q_max = mix["query_tokens_max"]
    q_len = np.clip(np.rint(stratified(lognormal_ppf(q_med, q_sigma), n, rng)),
                    QUERY_PREFIX_BYTES + 1, q_max).astype(np.int32)
    o_med, o_sigma = task["out_tokens"]
    out_tokens = np.maximum(1, np.rint(stratified(lognormal_ppf(o_med, o_sigma), n, rng))
                            ).astype(np.int32)

    difficulty = rng.beta(*task["difficulty"], size=n).astype(np.float32)
    topic = rng.integers(0, task["n_topics"], size=n).astype(np.int32)
    dim = EMBED_DIM
    centroids = rng.normal(0, 1.0, size=(task["n_topics"], dim)).astype(np.float32)
    diff_dir = rng.normal(0, 1.0, size=dim).astype(np.float32)
    diff_dir /= np.linalg.norm(diff_dir)
    noise = rng.normal(0, 0.55, size=(n, dim)).astype(np.float32)
    emb = (centroids[topic] + 2.2 * np.outer(difficulty - difficulty.mean(), diff_dir)
           + noise)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True) + 1e-8

    text_rng = rng_for(seed, "text")
    system_prompt = random_text(task["sys_tokens"] - 1, text_rng)   # + <bos>
    queries = [random_text(int(k) - QUERY_PREFIX_BYTES, text_rng) for k in q_len]
    seen: set = set()
    for i, q in enumerate(queries):         # every query a client sends is distinct
        while q in seen:
            q = random_text(len(q), text_rng)
        queries[i] = q
        seen.add(q)
    letters = "ABCDEFGHIJ"[: max(task["n_classes"], 1)]
    answers = [letters[i] for i in text_rng.integers(0, len(letters), n)]

    idx = rng.permutation(n)
    split = {"train": idx[: splits["train"]],
             "val": idx[splits["train"]: splits["train"] + splits["val"]],
             "test": idx[splits["train"] + splits["val"]:]}
    order = rng_for(seed, "order").permutation(split["test"])

    load = mix["load"]
    due = np.zeros(0)
    if load["loop"] == "open":
        due = open_loop_due(load["rate_qps"], seconds, rng_for(seed, "arrivals"))
    return Traffic(system_prompt=system_prompt, queries=queries, answers=answers,
                   in_tokens=q_len, out_tokens=out_tokens, difficulty=difficulty,
                   topic=topic, embeddings=emb.astype(np.float32), split=split,
                   order=order, due_s=due)


def prompt_bounds(mix: dict, batch_sizes) -> tuple[int, int]:
    """Shortest and longest batch prompt (tokens, <bos> and answer cue
    included) that the mix can produce at the given batch sizes."""
    task = mix["task"]
    q_med, q_sigma = task["query_tokens"]
    q_min = max(QUERY_PREFIX_BYTES + 1, int(np.rint(lognormal_ppf(q_med, q_sigma)(0.5 / 1e6))))
    q_max = mix["query_tokens_max"]
    cue = 3                                   # "\nA:"
    lo = task["sys_tokens"] + min(batch_sizes) * q_min + cue
    hi = task["sys_tokens"] + max(batch_sizes) * q_max + cue
    return lo, hi
