"""The one generator of serving traffic: reads a mix's parameters
(traffic/<name>.json) and the seed, gives the requests.

A mix fixes a pool of ``pool_requests`` sizes (prompt length, new
tokens) once, from quantiles of the stated distributions paired by the
mix's own ``pool_seed``.  Every run seed gets that same set of sizes in
another order and with other token ids: the seed changes which request
comes when, never how much work there is.  The loop is closed:
``clients`` callers, each sending its next request when its last one
finished.  Parameters:

    clients         callers in the closed loop
    prompt_len      {"dist": "loguniform"|"uniform", "min", "max"}
    new_tokens      the same
    pool_requests   sizes in the pool; pool_seed pairs them
"""
import math

import numpy as np


def _quantiles(spec, n):
    lo, hi = spec["min"], spec["max"]
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "loguniform":
        v = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif spec["dist"] == "uniform":
        v = lo + q * (hi - lo)
    else:
        raise ValueError(f"no distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(int)


def size_pool(traffic):
    """The mix's fixed sizes: (prompt_len, new_tokens) pairs."""
    n = traffic["pool_requests"]
    rs = np.random.RandomState(traffic["pool_seed"])
    prompts = _quantiles(traffic["prompt_len"], n)
    news = _quantiles(traffic["new_tokens"], n)
    return list(zip(prompts.tolist(), rs.permutation(news).tolist()))


class Plan:
    """The requests of one run, in the order they are to be sent."""

    def __init__(self, traffic, vocab, seed):
        self.traffic = traffic
        self.vocab = vocab
        self.rs = np.random.RandomState(seed % (2 ** 32))
        pool = size_pool(traffic)
        self.sizes = [pool[i] for i in self.rs.permutation(len(pool))]
        self.sent = 0
        self.clients = traffic["clients"]

    def next(self):
        """(prompt tokens, new tokens) of the next request; the pool
        is gone through again and again."""
        plen, new = self.sizes[self.sent % len(self.sizes)]
        toks = self.rs.randint(0, self.vocab, plen).astype(np.int32)
        self.sent += 1
        return toks, int(new)


def prefill_buckets(traffic, floor, ceiling):
    """The engine's power-of-two prefill buckets that the pool's
    prompts fall into."""
    def pow2(n):
        return 1 << max(0, int(n) - 1).bit_length()
    return sorted({min(max(pow2(p), pow2(floor)), pow2(ceiling))
                   for p, _ in size_pool(traffic)})
