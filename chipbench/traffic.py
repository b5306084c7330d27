"""The one general traffic generator: reads a mix's parameters, draws
nothing but order and token ids from ``--seed``.

Every seed gets the SAME set of sizes and arrival gaps, in another order:
lengths are the quantiles of the mix's clipped lognormal (a stratified
sample, not a random one), gaps the quantiles of the exponential at the
mix's rate. So two seeds do the same work and differ only in how it is
interleaved, and a cell's set of prompt buckets is the same in every run.
"""

from __future__ import annotations

import math

import numpy as np


def _norm_ppf(p):
    """Inverse normal CDF (Acklam's rational approximation, |err| < 1e-9
    after one Newton step is not needed here: lengths are rounded)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    if p < 0.02425:
        q = math.sqrt(-2 * math.log(p))
        return ((((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) /
                ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1))
    if p > 1 - 0.02425:
        return -_norm_ppf(1 - p)
    q = p - 0.5
    r = q * q
    return ((((((a[0]*r+a[1])*r+a[2])*r+a[3])*r+a[4])*r+a[5])*q /
            (((((b[0]*r+b[1])*r+b[2])*r+b[3])*r+b[4])*r+1))


def lognormal_quantiles(spec, n):
    """``n`` lengths: the (i+0.5)/n quantiles of lognormal(median, sigma),
    clipped to [lo, hi]."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * _norm_ppf((i + 0.5) / n))
        out.append(int(min(spec["hi"], max(spec["lo"], round(x)))))
    return out


def exponential_quantiles(rate, n):
    """``n`` inter-arrival gaps of a Poisson process at ``rate`` per
    second: the (i+0.5)/n quantiles, rescaled to the exact mean 1/rate."""
    gaps = np.array([-math.log(1 - (i + 0.5) / n) for i in range(n)])
    return gaps / gaps.mean() / rate


def requests(mix, seed, vocab, n):
    """``n`` requests: prompt token ids, output length, temperature, the
    request's own sampling seed. Sizes are fixed by the mix, their pairing
    and order by the seed. ``greedy_every`` makes every k-th request
    greedy (what the comparison with the reference can follow)."""
    rng = np.random.default_rng(seed)       # an int or a list of ints
    plens = np.array(lognormal_quantiles(mix["prompt_len"], n))[
        rng.permutation(n)]
    olens = np.array(lognormal_quantiles(mix["output_len"], n))[
        rng.permutation(n)]
    every = int(mix.get("greedy_every", 0))
    out = []
    for i in range(n):
        greedy = mix["temperature"] <= 0 or (every and i % every == 0)
        out.append({
            "prompt": rng.integers(1, vocab, size=int(plens[i])).tolist(),
            "max_new": int(olens[i]),
            "temperature": 0.0 if greedy else float(mix["temperature"]),
            "seed": int(rng.integers(0, 2 ** 31 - 1)),
        })
    return out


def arrivals(mix, seed, n):
    """Due times (seconds from window start) of ``n`` open-loop requests."""
    rng = np.random.default_rng([seed, 1])
    gaps = exponential_quantiles(mix["rate_per_s"], n)[rng.permutation(n)]
    return np.cumsum(gaps) - gaps[0]

