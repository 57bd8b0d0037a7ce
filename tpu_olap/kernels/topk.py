"""Top-K group selection (TopN queries).

Unlike Druid's *approximate* per-segment topN + broker re-rank (SURVEY.md
§8.4 #2), the dense group table makes exact top-K cheap: one lax.top_k
over the [K] metric array. Druid-approximate behavior is therefore a
strict-accuracy win, not a compatibility break; the context flag
`useApproximateTopN` exists for parity testing but maps to the same exact
kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_olap.kernels.hashing import has_x64


def top_k_groups(metric, present, threshold: int, inverted: bool):
    """metric: [K] values; present: [K] bool (group has rows).

    Returns (indices [threshold], valid [threshold]) — group ids of the
    top-`threshold` by metric (bottom if inverted), absent groups last.
    """
    k = min(int(threshold), metric.shape[-1])
    v = metric.astype(jnp.float64 if has_x64(jnp) else jnp.float32)
    v = jnp.where(present, -v if inverted else v, -jnp.inf)
    vals, order = jax.lax.top_k(v, k)
    valid = vals > -jnp.inf
    return order, valid
