"""What a configuration file states, in plain numbers.

Shared by the side that drives the program (``deploy.py``) and the side
that checks it (``srgrid.py`` and ``reference/``). Nothing here imports
the program.
"""

from __future__ import annotations

import math

__all__ = ["capacities", "bucket_capacity", "profile", "n_workers"]


def n_workers(doc: dict) -> int:
    return doc["n_i"] * (doc["n_i"] + doc["w"])


def capacities(doc: dict) -> tuple[int, int]:
    """Per-worker user and item table capacities.

    ``"capacity": "collision-free"`` is the only rule the benchmark
    knows: every id has a slot of its own on its worker, so no table
    ever evicts a tenant. Users are split over ``g = n_i + w`` groups
    and items over ``n_i`` splits; each count is rounded up to 128.
    """
    if doc["capacity"] != "collision-free":
        raise ValueError(f"unknown capacity rule {doc['capacity']!r}")
    g = doc["n_i"] + doc["w"]

    def up(n, k):
        return int(math.ceil(math.ceil(n / k) / 128) * 128)

    return up(doc["n_users"], g), up(doc["n_items"], doc["n_i"])


def bucket_capacity(doc: dict) -> int:
    """Events one worker takes per micro-batch (the rest is re-queued)."""
    fair = doc["micro_batch"] / n_workers(doc)
    return max(8, int(math.ceil(fair * doc["capacity_factor"])))


def profile(doc: dict) -> dict:
    """The stream profile (paper Table 1 statistics) of a deployment."""
    return {k: doc[k] for k in ("n_users", "n_items", "n_ratings",
                                "user_zipf", "item_zipf", "population_seed")}
