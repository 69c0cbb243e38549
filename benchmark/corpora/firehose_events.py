"""HTTP firehose events for the sketch configuration, stdlib only.

The shape of ``chip_smoke.sketch_corpus``: ``user`` uniform over
250,000 ids, ``path`` Zipf(1.2) over 10,000 paths (bounded here, where
the original took numpy's unbounded Zipf modulo 10,000), ``tenant``
skewed over four. Every record passes the chain, so every label is 1.
"""

import itertools
import random

from wire import KEEP

TENANTS = ("acme", "globex", "initech", "umbrella")
TENANT_WEIGHTS = (0.6, 0.25, 0.1, 0.05)


def make(n: int, seed: int, params: dict):
    rng = random.Random(seed)
    users = int(params.get("users", 250_000))
    paths = int(params.get("paths", 10_000))
    zipf_s = float(params.get("path_zipf_s", 1.2))
    cum = list(itertools.accumulate(
        1.0 / (k ** zipf_s) for k in range(1, paths + 1)))
    pth = rng.choices(range(paths), cum_weights=cum, k=n)
    ten = rng.choices(TENANTS, weights=TENANT_WEIGHTS, k=n)
    records = [{"user": "user-%06d" % rng.randrange(users),
                "path": "/api/v1/item/%d" % pth[i],
                "tenant": ten[i]} for i in range(n)]
    return records, bytes((KEEP,)) * n
