"""The one traffic generator: every mix is a file of parameters under
``perf/traffic/`` that this module reads; a new mix is a new file.

``token_rows``: ``[staged_batches * batch, seq_len]`` rows of ids for a
trainer (made by the training runner from the same parameters).

``closed_loop``: ``clients`` callers that each wait for their reply and
send the next request the moment it has come. The lengths are the cell's
and not the seed's: ``block`` prompt lengths and ``block`` output lengths
(the quantiles of the clipped log-normal the file names), paired and
ordered once by the file's ``order_seed`` and then repeated, so every
seed offers the same requests in the same order and only the token ids
(uniform over the vocabulary) and the weights differ. Measured on the
chip (PR 25): with the order drawn from the seed, 40-second windows of
different seeds spread by 4% in tokens per second and 30% in the 95th
percentile of the time to first token.
"""

from __future__ import annotations

import statistics

import numpy as np

from . import weights


def length_set(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n of a log-normal with
    the given median and sigma, clipped to [min, max]."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def requests(traffic: dict, seed: int, vocab: int):
    """An endless stream of ``(prompt ids int32 [p], max_new_tokens)``."""
    if traffic["kind"] != "closed_loop":
        raise ValueError(f"no request stream for kind {traffic['kind']!r}")
    order = np.random.default_rng(traffic["order_seed"])
    n = traffic["block"]
    prompts, outputs = (order.permutation(length_set(traffic[k], n))
                        for k in ("prompt", "output"))
    rng = weights.host_rng(seed, 2)
    while True:
        for p, o in zip(prompts, outputs):
            yield rng.integers(0, vocab, int(p), dtype=np.int32), int(o)
