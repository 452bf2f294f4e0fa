"""The one general traffic generator. A traffic file gives the
parameters; the seed gives the order and the token values.

Every seed gets the same set of sizes and of gaps between arrivals, in
another order: the lengths are the quantiles of the file's clipped
log-normal distributions and the gaps the quantiles of the exponential
distribution at the file's rate, so that two runs differ in what meets
what, never in how much work the window holds.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(n, median, sigma, lo, hi, round_to=1):
    """n lengths: the (i + 0.5)/n quantiles of a log-normal with this
    median and sigma, rounded to a multiple of `round_to` and clipped to
    [lo, hi]."""
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        length = round(median * math.exp(sigma * z) / round_to) * round_to
        out.append(int(min(hi, max(lo, length))))
    return out


def lengths(spec, n):
    return lognormal_quantiles(n, spec["median"], spec["sigma"], spec["min"],
                               spec["max"], spec.get("round_to", 1))


def exponential_quantile_gaps(n, rate):
    """n gaps whose sum is close to n / rate."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def requests(traffic, vocab, seed, n, stream=0):
    """n requests as (prompt tokens, max_new_tokens). `stream` separates
    the draws of warm-up and window."""
    prompts = lengths(traffic["prompt_tokens"], n)
    outputs = lengths(traffic["output_tokens"], n)
    # which output length meets which prompt length is fixed, not seeded
    pairing = np.random.default_rng(n).permutation(n)
    rng = np.random.default_rng([int(seed), int(stream)])
    order = rng.permutation(n)
    out = []
    for i in order:
        tokens = rng.integers(1, vocab, prompts[i]).tolist()
        out.append((tokens, outputs[pairing[i]]))
    return out


def arrivals(rate, n, seed):
    """Due times, in seconds from the window's start, of n requests at
    `rate` a second."""
    gaps = np.array(exponential_quantile_gaps(n, rate))
    rng = np.random.default_rng([int(seed), 7])
    return np.cumsum(rng.permutation(gaps)).tolist()


def zipf_corpus(vocab, n_tokens, exponent, seed):
    """Training data: a seeded corpus whose unigram distribution is steep
    enough for a few steps to lower the loss (the benchmark's own copy of
    tests/flows/chip_smoke_flow.py's zipf_corpus)."""
    rng = np.random.default_rng(seed)
    return np.minimum(rng.zipf(exponent, n_tokens) - 1,
                      vocab - 1).astype(np.int32)
