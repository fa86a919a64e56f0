"""What a configuration file states, in plain numbers.

Shared by the side that drives the program (``deploy.py``) and the side
that checks it (``srgrid.py`` and ``reference/``). Nothing here imports
the program.
"""

from __future__ import annotations

import math

__all__ = ["capacities", "forgetting", "validate", "bucket_capacity",
           "profile", "n_workers"]

#: Forgetting policies a reference replays, each with its threshold key.
FORGETTING = {"lru": "lru_max_age", "lfu": "lfu_min_freq"}

#: Settings of the program that no reference replays yet.
UNREPLAYED = {"drift": "the adaptive drift controller",
              "storage": "a storage policy other than the default"}


def n_workers(doc: dict) -> int:
    return doc["n_i"] * (doc["n_i"] + doc["w"])


def capacities(doc: dict) -> tuple[int, int]:
    """Per-worker user and item table capacities.

    ``"capacity": "collision-free"``: every id has a slot of its own on
    its worker, so no table ever evicts a tenant. Users are split over
    ``g = n_i + w`` groups and items over ``n_i`` splits; each count is
    rounded up to 128. ``"capacity": {"u_cap": N, "i_cap": M}``: those
    capacities, each a multiple of 128 and no larger than the
    collision-free one, so ids that share a slot evict each other.
    """
    def up(n, k):
        return int(math.ceil(math.ceil(n / k) / 128) * 128)

    free = (up(doc["n_users"], doc["n_i"] + doc["w"]),
            up(doc["n_items"], doc["n_i"]))
    rule = doc["capacity"]
    if rule == "collision-free":
        return free
    if not isinstance(rule, dict) or set(rule) != {"u_cap", "i_cap"}:
        raise ValueError(f"capacity rule {rule!r}: 'collision-free' or "
                         "{'u_cap': N, 'i_cap': M}")
    caps = (rule["u_cap"], rule["i_cap"])
    for name, cap, most in zip(("u_cap", "i_cap"), caps, free):
        if (not isinstance(cap, int) or cap <= 0 or cap % 128
                or cap > most):
            raise ValueError(f"capacity {name} {cap!r}: a positive multiple "
                             f"of 128, at most the collision-free {most}")
    return caps


def forgetting(doc: dict) -> dict | None:
    """The fixed-cadence forgetting the configuration states, checked;
    ``None`` where it states none.

    ``{"policy": "lru", "trigger_every": c, "lru_max_age": a}`` or
    ``{"policy": "lfu", "trigger_every": c, "lfu_min_freq": f}`` (paper
    Sec. 5.2): every ``c`` events the stream folds in, each worker
    evicts its live entries untouched for more than ``a`` of its events
    (LRU) or seen fewer than ``f`` times (LFU). ``c`` is at least a
    micro-batch, so that a trigger falls every ``c`` events to within a
    step.
    """
    f = doc.get("forgetting")
    if f is None:
        return None
    policy = f.get("policy") if isinstance(f, dict) else None
    if policy not in FORGETTING:
        raise ValueError(f"forgetting policy {policy!r} has no reference "
                         f"replay; one of {sorted(FORGETTING)}")
    key = FORGETTING[policy]
    if set(f) != {"policy", "trigger_every", key}:
        raise ValueError(f"forgetting {f!r}: exactly policy, trigger_every "
                         f"and {key}")
    for name, least in (("trigger_every", doc["micro_batch"]),
                        (key, 1 if policy == "lfu" else 0)):
        if not isinstance(f[name], int) or f[name] < least:
            raise ValueError(f"forgetting {name} {f[name]!r}: a whole "
                             f"number, at least {least}")
    if doc.get("warm_periods", 0):
        raise ValueError("forgetting with warm_periods: the warm start "
                         "replays no forgetting")
    return f


def validate(doc: dict) -> None:
    """Raise ``ValueError``, naming the setting, where the configuration
    states what no reference replays."""
    for key, what in UNREPLAYED.items():
        if key in doc:
            raise ValueError(f"{key!r}: {what} has no reference replay")
    capacities(doc)
    forgetting(doc)


def bucket_capacity(doc: dict) -> int:
    """Events one worker takes per micro-batch (the rest is re-queued)."""
    fair = doc["micro_batch"] / n_workers(doc)
    return max(8, int(math.ceil(fair * doc["capacity_factor"])))


def profile(doc: dict) -> dict:
    """The stream profile (paper Table 1 statistics) of a deployment."""
    return {k: doc[k] for k in ("n_users", "n_items", "n_ratings",
                                "user_zipf", "item_zipf", "population_seed")}
