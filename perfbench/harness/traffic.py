"""One general traffic generator; a mix is a data file under `traffic/`.

Every seed sees the same work: lengths and gaps between arrivals are the
quantiles of the mix's distributions (stratified, so the histogram is the same
for every seed), put in an order, and the seed draws the token ids. With
`order_seed` the order is the mix's own, the same for every seed: at 1-2
requests a second that live 14 s each, the tokens that fall inside a 50 s
window swing by 6 % with the order alone (my chip runs, PR 24), more than any
bound could cover. Without it the seed also permutes the order. A mix's file
gives:

  kind            "open_loop" (requests on a schedule) or "train_rows"
  rate_per_s      arrivals per second (open loop); fixed, never searched
  prompt_tokens   {"dist": "lognormal"|"fixed", "median", "sigma", "min", "max"}
  output_tokens   the same
  order_seed      optional: fixes the order of lengths and gaps for every seed
  burst           optional {"size": n}: arrivals come n at a time, same mean rate
  shared_prefix   optional {"count": k, "tokens": n, "share": p}: a share p of
                  the requests start with one of k fixed prefixes of n tokens
  lead_in_s       optional: seconds of the same traffic offered before the
                  window opens (set-up), so that the window starts on an engine
                  as full as the rate keeps it
  wait_first_tokens_s  how long past the close the run waits for first tokens
  seq_len, id_max (train_rows) row length and the bound of the token ids
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def token_row(seed: int, index: int, length: int, id_max: int) -> np.ndarray:
    """Row `index` of the training stream of `seed`: every row differs."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(0, id_max, length, dtype=np.int32)


def train_batch(seed: int, step: int, batch: int, seq: int, id_max: int):
    """(inputs, labels) of training step `step`, as the loader deals them."""
    rows = np.stack([token_row(seed, step * batch + r, seq + 1, id_max)
                     for r in range(batch)])
    return rows[:, :-1], rows[:, 1:]


def _quantiles(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]))
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def open_loop(mix: dict, seconds: float, seed: int, vocab: int) -> list:
    """[{"due", "prompt", "max_new"}] sorted by `due` (seconds from the
    window's start), all due inside the window."""
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(mix.get("order_seed", seed)) + 1)
    rate = float(mix["rate_per_s"])
    burst = int((mix.get("burst") or {}).get("size", 1))
    n = max(1, round(rate * seconds))
    groups = max(1, n // burst)
    # stratified exponential gaps, scaled to fill the window exactly
    gaps = -np.log1p(-(np.arange(groups) + 0.5) / groups)
    gaps *= seconds / gaps.sum()
    gaps = order.permutation(gaps)
    starts = np.cumsum(gaps) - gaps[0] * 0.5 - (gaps[-1] * 0.5 if groups > 1
                                                else 0.0)
    starts = np.clip(starts, 0.0, seconds)
    due = np.repeat(starts, burst)[:n] if burst > 1 else starts
    n = len(due)
    prompts = order.permutation(_quantiles(mix["prompt_tokens"], n))
    outs = order.permutation(_quantiles(mix["output_tokens"], n))
    sp = mix.get("shared_prefix") or {}
    prefixes = [rng.integers(1, vocab, int(sp["tokens"])).tolist()
                for _ in range(int(sp.get("count", 0)))]
    reqs = []
    for i in range(n):
        ids = rng.integers(1, vocab, int(prompts[i])).tolist()
        if prefixes and rng.random() < float(sp.get("share", 0.0)):
            pre = prefixes[int(rng.integers(len(prefixes)))]
            ids = (pre + ids)[:max(len(ids), len(pre) + 1)]
        reqs.append({"due": float(due[i]), "prompt": ids,
                     "max_new": int(outs[i])})
    reqs.sort(key=lambda r: r["due"])
    return reqs
