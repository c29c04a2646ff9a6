"""Reference of the §5.4 join aggregate: the sum, over every S tuple whose
key R holds, of R's value times S's value (R has unique keys).  The same
answer ``chip_smoke.py``'s OLAP phase checks with ``np.isin``, with the
value columns carried along, computed by a lookup table over R's keys."""
from __future__ import annotations

import numpy as np


def join_aggregate(rk, rv, sk, sv) -> int:
    """Exact aggregate of R ⋈ S as a Python int.  R's keys must be unique
    and positive."""
    rk = np.asarray(rk, np.int64)
    if rk.min() < 1 or np.bincount(rk).max() > 1:
        raise ValueError("R's keys are not unique and positive")
    lut = np.zeros(int(rk.max()) + 1, np.uint64)
    lut[rk] = np.asarray(rv, np.uint64)
    sk = np.asarray(sk, np.int64)
    inside = sk < lut.size
    prod = lut[sk[inside]] * np.asarray(sv, np.uint64)[inside]
    return int(prod.sum(dtype=np.uint64))
