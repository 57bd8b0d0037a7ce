"""Top-K group selection (TopN queries).

Unlike Druid's *approximate* per-segment topN + broker re-rank (SURVEY.md
§8.4 #2), a whole group table on one device makes exact top-K cheap: one
lax.top_k over the table's metric column. Druid-approximate behavior is
therefore a strict-accuracy win, not a compatibility break; the context
flag `useApproximateTopN` exists for parity testing but maps to the same
exact kernel.

The caller is the sparse program that ends in a TopN's threshold
(`sparse_groupby.sparse_group_reduce` with `top`), which ranks BEFORE it
builds the tables it does not rank: `metric` is the ranked aggregate's
[cap] table, `present` the slots whose sorted run is not empty, and the
indices returned are the slots every other table is then read at. Slot i
holds the i-th smallest present key, and lax.top_k puts the lower index
first among equal values, so ties at the threshold are kept in the
dimension's own ascending order — the rule of the host assembler
(`runner._emit_topn`, a stable argsort over label order).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def top_k_groups(metric, present, threshold: int, inverted: bool):
    """metric: [K] values; present: [K] bool (group has rows).

    Returns (indices [threshold], valid [threshold]) — group ids of the
    top-`threshold` by metric (bottom if inverted), absent groups last.
    An integer metric is ranked as the integer it is (exact past 2^53).
    """
    k = min(int(threshold), metric.shape[-1])
    v = -metric if inverted else metric
    lowest = np.iinfo(v.dtype).min if jnp.issubdtype(v.dtype, jnp.integer) \
        else -jnp.inf
    _, order = jax.lax.top_k(jnp.where(present, v, lowest), k)
    return order, present[order]
