"""Federated token shards for next-token training: packed documents of
Zipf-distributed token ids, one satellite's shard a writer's text.

Seeded, vectorised over each satellite's tokens, NumPy only. What a
training job's data looks like (documents of heavy-tailed length packed
into fixed rows) and how federated text is split (one client is one
author or device, with heavy-tailed shard sizes):

- rows a satellite: lognormal, median `median_rows` and log-sd
  `shard_sigma`, rounded and clipped to [`min_rows`, `max_rows`]. LEAF's
  Shakespeare split (arXiv:1812.01097, Table 1: 3,743 samples a device on
  average, standard deviation 6,212) has log-sd 1.15;
- documents: lognormal lengths in tokens, median `doc_median` and log-sd
  `doc_sigma`, each followed by the end-of-document token `EOS`, packed
  back to back into rows of seq_len + 1 tokens; a document runs on from
  one row into the next;
- token ids: Zipf over ranks, exponent `zipf_a` (word frequencies follow
  a Zipf law with exponent near 1: Piantadosi 2014,
  doi:10.3758/s13423-014-0585-6), over the configuration's `vocab_size`
  ids (its slice of the vocabulary, where sliced) less `EOS`;
- held-out rows: `eval_rows` a satellite, made the same way from a stream
  of their own.

The mix's `data` block gives the keywords; the configuration gives
`vocab_size` and `seq_len`. A satellite's shard depends only on the seed,
its index and those numbers.
"""
from __future__ import annotations

import numpy as np

EOS = 0     # the end-of-document id; content ids are 1 .. vocab_size - 1


def zipf_cdf(vocab_size: int, zipf_a: float) -> np.ndarray:
    """Cumulative probabilities of the content ids 1 .. vocab_size - 1,
    by rank: id r has probability proportional to r ** -zipf_a."""
    p = np.arange(1, vocab_size, dtype=np.float64) ** -float(zipf_a)
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    return cdf


def _stream(rng: np.random.Generator, n_tokens: int, cdf: np.ndarray,
            doc_median: float, doc_sigma: float) -> np.ndarray:
    """`n_tokens` ids of packed documents: Zipf ids with `EOS` closing
    each document."""
    ids = 1 + np.searchsorted(cdf, rng.random(n_tokens), side="right")
    mean_doc = doc_median * np.exp(doc_sigma ** 2 / 2)
    draws = int(n_tokens / (mean_doc + 1)) + 16
    last = -1
    while last < n_tokens - 1:       # documents until the stream is full
        lengths = np.maximum(1, np.rint(rng.lognormal(
            np.log(doc_median), doc_sigma, draws))).astype(np.int64)
        ends = last + np.cumsum(lengths + 1)
        ids[ends[ends < n_tokens]] = EOS
        last = int(ends[-1])
    return ids.astype(np.int32)


def generate(n_clients: int, seed: int, cfg: dict, *, median_rows: float,
             shard_sigma: float, min_rows: int, max_rows: int,
             eval_rows: int, doc_median: float, doc_sigma: float,
             zipf_a: float) -> dict[str, np.ndarray]:
    """Stacked client shards, padded to `max_rows` rows of
    `cfg["seq_len"] + 1` ids.

    Returns x (K, N, S+1) int32, y (K, N) zeros, n (K,), and the held-out
    x_eval (K, eval_rows, S+1), y_eval, n_eval, all as numpy arrays.
    """
    vocab, width = int(cfg["vocab_size"]), int(cfg["seq_len"]) + 1
    if vocab < 2 or not 1 <= min_rows <= max_rows:
        raise ValueError(f"vocab_size {vocab} and rows [{min_rows}, "
                         f"{max_rows}] make no shard")
    cdf = zipf_cdf(vocab, zipf_a)
    x = np.zeros((n_clients, max_rows, width), np.int32)
    n = np.zeros((n_clients,), np.int32)
    xe = np.zeros((n_clients, eval_rows, width), np.int32)
    for k in range(n_clients):
        rng = np.random.default_rng(np.random.SeedSequence([seed, k]))
        nk = int(np.clip(np.rint(rng.lognormal(np.log(median_rows),
                                               shard_sigma)),
                         min_rows, max_rows))
        x[k, :nk] = _stream(rng, nk * width, cdf, doc_median,
                            doc_sigma).reshape(nk, width)
        xe[k] = _stream(rng, eval_rows * width, cdf, doc_median,
                        doc_sigma).reshape(eval_rows, width)
        n[k] = nk
    return dict(x=x, y=np.zeros(x.shape[:2], np.int32), n=n, x_eval=xe,
                y_eval=np.zeros(xe.shape[:2], np.int32),
                n_eval=np.full((n_clients,), eval_rows, np.int32))
