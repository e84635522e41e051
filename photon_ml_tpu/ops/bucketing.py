"""The bucket rule: cut ascending sizes into contiguous groups, each padded
to its largest member, so that the padded total is least.

One decision with two users: the GAME data builder buckets entities by
their row counts (``game/data.py``), and the hot/cold split buckets rows by
their cold-slot counts (``ops/sparse.py``). Host-side numpy over the
DISTINCT sizes, which are few even when the entries number millions.
"""

from __future__ import annotations

import numpy as np


def split_minimizing_padding(sorted_counts: np.ndarray, max_buckets: int):
    """Optimal contiguous split of ascending per-entity row counts into at
    most `max_buckets` groups minimizing total padded slots
    Σ_b |entities_b| · max_count_b (exact DP over distinct counts — the
    number of distinct entity sizes is small even when entities number
    millions). Returns [(lo, hi)) index ranges into sorted_counts."""
    if sorted_counts.size == 0:
        return []
    values, nums = np.unique(sorted_counts, return_counts=True)
    return split_histogram_minimizing_padding(values, nums, max_buckets)


def split_histogram_minimizing_padding(
    values: np.ndarray, nums: np.ndarray, max_buckets: int
):
    """``split_minimizing_padding`` from the counts' histogram: ascending
    distinct ``values``, each held by ``nums`` entries (all above zero)."""
    m = values.size
    k = min(max_buckets, m)
    prefix = np.concatenate([[0], np.cumsum(nums)])
    INF = float("inf")
    # dp[j] = min cost covering distinct values [0, j) ; rebuilt per layer
    dp = np.full(m + 1, INF)
    dp[0] = 0.0
    choice = np.zeros((k, m + 1), np.int64)
    for layer in range(k):
        nxt = np.full(m + 1, INF)
        for j in range(1, m + 1):
            # bucket = distinct values [i, j) with cap values[j-1]
            costs = dp[:j] + (prefix[j] - prefix[:j]) * values[j - 1]
            i = int(np.argmin(costs))
            nxt[j] = costs[i]
            choice[layer, j] = i
        dp = nxt
    # backtrack
    bounds = []
    j = m
    layer = k - 1
    while j > 0:
        i = int(choice[layer, j])
        bounds.append((int(prefix[i]), int(prefix[j])))
        j = i
        layer -= 1
    return bounds[::-1]
