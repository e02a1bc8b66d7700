"""The traffic of one cell, generated from ``--seed``.

A traffic mix is a data file (``bench/traffic/<mix>.json``) of parameters:
the cohort, the local work per client, the compression schedule and the
client links. This module is the one generator that reads them. It keeps
the benchmark's own copies of the program's generators, so that the inputs
of a cell do not move when the program changes:

* client links: the arithmetic of ``core.cost_model.sample_link_arrays``
  (normal bandwidth clipped at 0.05 Mbit/s, uniform latency);
* per-round compression ratios and merge weights: the BCRS schedule of
  ``core.bcrs.make_schedule_batch`` (paper Alg. 2 and Eq. 6), or the data
  fractions for a dense merge;
* tokens: ``data.synthetic.synthetic_lm_tokens`` (Zipf unigrams and a
  planted bigram permutation), drawing the same values with the sampling
  table built once instead of once per position.

Every round has the same shapes; only the values change with the round and
the seed.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

LINK_TAG = 7_001
SELECT_TAG = 27_449
TOKEN_TAG = 104_729


def sample_links(n: int, rng: np.random.Generator, bw_mean_mbps: float,
                 bw_sd_mbps: float, lat_lo: float, lat_hi: float):
    """(bandwidth bit/s, latency s) float64 columns for ``n`` clients."""
    bw = np.maximum(rng.normal(bw_mean_mbps, bw_sd_mbps, n), 0.05) * 1e6
    lat = rng.uniform(lat_lo, lat_hi, n)
    return bw, lat


def bcrs_schedule(bw: np.ndarray, lat: np.ndarray, fracs: np.ndarray,
                  v_bytes: float, cr_star: float, alpha: float,
                  cr_max: float = 1.0):
    """Paper Alg. 2 and Eq. 6 for one round's cohort.

    Every client's ratio is raised until its upload ends with the slowest
    client's upload at ``cr_star``: CR_i = (T_bench - L_i) B_i / (2 V), clipped
    to [cr_star, cr_max]. The merge coefficient is
    p'_i = f_i / max(f_i, CR_i / sum CR) * alpha. Returns (crs, coeffs).
    """
    v_bits = 8.0 * v_bytes
    times = lat + 2.0 * v_bits * cr_star / bw
    t_bench = times.max()
    crs = np.clip((t_bench - lat) * bw / (2.0 * v_bits), cr_star, cr_max)
    s = crs.sum()
    ncr = crs / s if s > 0 else crs
    coeffs = fracs / np.maximum(fracs, ncr) * alpha
    return crs, coeffs


def zipf_cdf(vocab: int) -> np.ndarray:
    """The sampling table of ``Generator.choice(vocab, p=1/rank)``."""
    probs = 1.0 / np.arange(1, vocab + 1)
    probs /= probs.sum()
    cdf = probs.cumsum()
    cdf /= cdf[-1]
    return cdf


def lm_tokens(n_seqs: int, seq_len: int, vocab: int, rng: np.random.Generator,
              cdf: np.ndarray) -> np.ndarray:
    """Zipf unigrams with a planted bigram: token t+1 follows a fixed
    permutation of token t with probability 0.5. Draws the same values as
    ``rng.choice(vocab, n, p=probs)`` would, position by position."""
    perm = rng.permutation(vocab)
    toks = np.empty((n_seqs, seq_len), np.int32)
    toks[:, 0] = cdf.searchsorted(rng.random(n_seqs), side="right")
    for t in range(1, seq_len):
        follow = rng.random(n_seqs) < 0.5
        fresh = cdf.searchsorted(rng.random(n_seqs), side="right")
        toks[:, t] = np.where(follow, perm[toks[:, t - 1]], fresh)
    return toks


class Traffic:
    """Per-round inputs of one cell: ``round(r)`` and ``chunk(r0, n)``.

    ``mix`` is the traffic file's content, ``vocab`` the configuration's
    vocabulary and ``n_params`` its parameter count (the update size V the
    schedule prices, at 4 bytes per parameter as the program's driver
    prices it).
    """

    def __init__(self, mix: dict, vocab: int, n_params: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = int(seed)
        self.clients = int(mix["clients"])
        self.population = int(mix["population"])
        links = mix["links"]
        self.bw, self.lat = sample_links(
            self.population, np.random.default_rng((self.seed, LINK_TAG)),
            links["bw_mean_mbps"], links["bw_sd_mbps"], links["lat_lo_s"],
            links["lat_hi_s"])
        self.v_bytes = 4.0 * n_params
        self.cdf = zipf_cdf(vocab)

    def round(self, r: int) -> Dict[str, np.ndarray]:
        """Round ``r``'s inputs: tokens/labels [C, S, B, T] int32, step_mask
        [C, S], active [C], weights [C] f32, crs [C] f32."""
        mix, c = self.mix, self.clients
        sel = np.random.default_rng((self.seed, SELECT_TAG, r)).choice(
            self.population, c, replace=False)
        fracs = np.full(c, 1.0 / c)
        if mix["weighting"] == "bcrs":
            crs, weights = bcrs_schedule(self.bw[sel], self.lat[sel], fracs,
                                         self.v_bytes, mix["cr"],
                                         mix["alpha"])
        else:
            weights = fracs
            crs = np.full(c, mix["cr"] if mix["compress"] else 1.0)
        s, b, t = mix["local_steps"], mix["batch"], mix["seq"]
        toks = lm_tokens(c * s * b, t + 1, self.vocab,
                         np.random.default_rng((self.seed, TOKEN_TAG, r)),
                         self.cdf).reshape(c, s, b, t + 1)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:],
                "step_mask": np.ones((c, s), bool),
                "active": np.ones((c,), bool),
                "weights": weights.astype(np.float32),
                "crs": crs.astype(np.float32)}

    def chunk(self, r0: int, n: int) -> Dict[str, np.ndarray]:
        """Rounds r0 .. r0+n-1 stacked on a leading axis."""
        rounds = [self.round(r) for r in range(r0, r0 + n)]
        return {k: np.stack([x[k] for x in rounds]) for k in rounds[0]}
