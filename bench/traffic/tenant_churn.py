"""Open-loop requests to tenants of one model, with the active tenant
switching over time.

A mix file of kind ``tenant_churn`` gives:

- ``rate``: requests per second, Poisson arrivals;
- ``prompt_len``: tokens in every prompt;
- ``new_tokens``: ``{"median", "sigma", "min", "max"}`` of a clipped
  lognormal output length;
- ``score_share``: share of requests to the ``score`` entry (the rest
  call ``generate``);
- ``tenants``: how many copies of the model, each with its own weights;
- ``switch_mean_s``: mean seconds between switches of the active
  tenant, Poisson (0: tenant 0 alone); the next tenant is drawn over
  the others with weight 1 / (rank + 1) ** ``zipf_s``;
- ``profile_requests``: requests the profile pass serves in set-up;
- ``resident`` (optional): tenant 0 is cold-started in set-up and
  serves each entry once there, so the window starts warm;
- ``timeline_seed``: the seed that orders arrivals, output lengths,
  entries and switches, so every run replays one timeline; the run's
  seed draws the prompts (and the harness the tenants' weights).

Every seed gets the same work: the gaps between arrivals, the output
lengths and the gaps between switches are the quantiles of their
distributions at fixed points, in the order ``timeline_seed`` gives.
Ordered by the run's seed, they would spread the latency tail from run
to run by the order alone (where a short switch gap meets a burst of
arrivals, requests queue behind two cold starts).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def exponential_gaps(n: int, mean: float, rng) -> np.ndarray:
    return rng.permutation(-mean * np.log1p(-_midpoints(n)))


def output_lengths(n: int, spec: dict, rng) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(q) for q in _midpoints(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return rng.permutation(
        np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int))


def _entries(n: int, share: float, rng, at_least_one: bool) -> list[str]:
    k = int(round(share * n))
    if at_least_one and share > 0:
        k = max(k, 1)
    scored = set(rng.choice(n, size=k, replace=False).tolist())
    return ["score" if i in scored else "generate" for i in range(n)]


def _tenant_timeline(mix: dict, seconds: float, rng):
    """[(from_s, tenant)] with the first tenant at 0."""
    n_t, mean = mix["tenants"], mix["switch_mean_s"]
    weights = 1.0 / (np.arange(n_t) + 1.0) ** mix.get("zipf_s", 1.0)
    cur = int(rng.choice(n_t, p=weights / weights.sum()))
    out = [(0.0, cur)]
    if n_t < 2 or not mean:
        return out
    n_sw = max(int(round(seconds / mean)), 1)
    times = np.cumsum(exponential_gaps(n_sw, mean, rng))[:-1]
    for t in times:
        others = [i for i in range(n_t) if i != cur]
        w = weights[others] / weights[others].sum()
        cur = int(others[rng.choice(len(others), p=w)])
        out.append((float(t), cur))
    return out


def _prompts(n: int, mix: dict, vocab: int, rng) -> np.ndarray:
    return rng.integers(0, vocab, (n, mix["prompt_len"]), dtype=np.int32)


def schedule(mix: dict, seed: int, seconds: float, vocab: int):
    """The window's requests in arrival order: dicts with ``due`` (s
    from the window's open), ``tenant``, ``entry``, ``new_tokens`` and
    ``prompt``.  All are due before ``seconds``."""
    n = max(int(round(mix["rate"] * seconds)), 1)
    order = mix["timeline_seed"]
    gaps = exponential_gaps(n, 1.0 / mix["rate"], _rng(order, 0))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= min(1.0, 0.999 * seconds / max(due[-1], 1e-9))
    lengths = output_lengths(n, mix["new_tokens"], _rng(order, 1))
    entries = _entries(n, mix["score_share"], _rng(order, 2), True)
    timeline = _tenant_timeline(mix, seconds, _rng(order, 3))
    prompts = _prompts(n, mix, vocab, _rng(seed, 4))
    out = []
    for i in range(n):
        tenant = [t for start, t in timeline if start <= due[i]][-1]
        out.append({"due": float(due[i]), "tenant": tenant,
                    "entry": entries[i], "new_tokens": int(lengths[i]),
                    "prompt": prompts[i]})
    return out


def profile_sample(mix: dict, seed: int, vocab: int):
    """What the profile pass serves: ``profile_requests`` requests of
    the mix, entries in their shares (rounded, so a rare entry may get
    none, as in a short profile of real traffic)."""
    n = mix["profile_requests"]
    lengths = output_lengths(n, mix["new_tokens"], _rng(seed, 11))
    entries = _entries(n, mix["score_share"], _rng(seed, 12), False)
    prompts = _prompts(n, mix, vocab, _rng(seed, 14))
    return [{"entry": e, "new_tokens": int(k), "prompt": p}
            for e, k, p in zip(entries, lengths, prompts)]
