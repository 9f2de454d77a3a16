"""The one general traffic generator: everything a cell sends is a function
of its traffic file's numbers and ``--seed``, and of nothing else.

* token rows for jobs (fine-tune, batch generation): ids in [2, vocab), no
  pad and no EOS, drawn in bulk;
* an open-loop schedule for serving: arrival times, prompt lengths and output
  budgets.  Arrivals are a Poisson process conditioned on its count
  (``round(rate * seconds)`` sorted uniform times), and each length
  distribution is sampled at evenly spaced quantiles and then shuffled, so
  every seed offers the same amount of work in another order.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np


def token_rows(rng: np.random.Generator, n: int, vocab: int, enc_len: int,
               dec_len: Optional[int]) -> Dict[str, np.ndarray]:
    """``n`` synthetic rows as columns of int32 arrays."""
    cols = {"input_ids": rng.integers(2, vocab, (n, enc_len), np.int32),
            "attention_mask": np.ones((n, enc_len), np.int32)}
    if dec_len is not None:
        cols["labels"] = rng.integers(2, vocab, (n, dec_len), np.int32)
    return cols


def as_items(cols: Dict[str, np.ndarray]) -> List[Dict[str, np.ndarray]]:
    n = len(next(iter(cols.values())))
    return [{k: v[i] for k, v in cols.items()} for i in range(n)]


def lognormal_lengths(rng: np.random.Generator, n: int,
                      spec: Dict[str, Any]) -> np.ndarray:
    """``n`` whole lengths from a log-normal given as ``{"median", "sigma",
    "min", "max"}``: its quantiles (i + 0.5) / n, clipped, shuffled."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    rng.shuffle(x)
    return x


def open_loop_schedule(params: Dict[str, Any], seed: int, seconds: float,
                       vocab: int) -> List[Dict[str, Any]]:
    """Requests due in ``[0, seconds)``: ``{"due_s", "prompt",
    "max_new_tokens", "priority"}``, ordered by due time.  A function of the
    arguments alone."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    n = max(1, int(round(float(params["rate_rps"]) * seconds)))
    due = np.sort(rng.uniform(0.0, seconds, n))
    plen = lognormal_lengths(rng, n, params["prompt_len"])
    budget = lognormal_lengths(rng, n, params["output_len"])
    out = []
    for i in range(n):
        prompt = rng.integers(2, vocab, int(plen[i])).tolist()
        out.append({"due_s": float(due[i]), "prompt": prompt,
                    "max_new_tokens": int(budget[i]),
                    "priority": params.get("priority", "interactive")})
    return out
