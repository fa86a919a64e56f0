"""The system under test, built from a configuration file alone.

The only module of the benchmark that reaches into the program: it
turns a configuration file (``configs/<name>.json``) and a traffic mix's
publish policy into a ``repro.StreamSession`` (fresh, or restored from a
checkpoint of a state the benchmark made), and reads back the session's
state as named host arrays for the output check.
"""

from __future__ import annotations

import shutil
import tempfile
import warnings

import cellspec

__all__ = ["stream_config", "session", "restored_session", "host_states"]


def stream_config(doc: dict):
    from repro.core.algorithm import get_algorithm
    from repro.core.forgetting import ForgettingConfig
    from repro.core.pipeline import StreamConfig
    from repro.core.routing import GridSpec

    u_cap, i_cap = cellspec.capacities(doc)
    hyper = get_algorithm(doc["algorithm"]).default_hyper()._replace(
        **doc["hyper"], u_cap=u_cap, i_cap=i_cap)
    forget = cellspec.forgetting(doc)
    extra = ({} if forget is None
             else {"forgetting": ForgettingConfig(**forget)})
    return StreamConfig(algorithm=doc["algorithm"],
                        grid=GridSpec(doc["n_i"], doc["w"]),
                        micro_batch=doc["micro_batch"],
                        capacity_factor=doc["capacity_factor"], hyper=hyper,
                        backend=doc["backend"], seed=doc["model_seed"],
                        **extra)


def _checked_config(doc: dict):
    """The configuration, after checking that the program runs the
    backend it states (the program degrades with a warning)."""
    from repro.core import algorithm as algorithm_lib

    cfg = stream_config(doc)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        backend = algorithm_lib.negotiated_backend(cfg)
    if backend != doc["backend"]:
        raise RuntimeError(f"the program runs backend {backend!r}, "
                           f"the configuration states {doc['backend']!r}")
    return cfg


def _policy(publish: dict):
    from repro.serve import PublishPolicy

    return PublishPolicy(every=publish["every"], mode=publish["mode"])


def session(doc: dict, publish: dict):
    """A fresh ``StreamSession`` on the backend the configuration names."""
    from repro import StreamSession

    return StreamSession(_checked_config(doc), publish=_policy(publish))


def restored_session(doc: dict, publish: dict, leaves: dict,
                     events_processed: int):
    """A ``StreamSession`` resumed, through the program's checkpoint
    restore, from the state ``leaves`` (named as :func:`host_states`
    names them) after ``events_processed`` events; the checkpoint is
    written to a temporary directory and removed."""
    import jax
    import jax.numpy as jnp

    from repro import StreamSession
    from repro.core import pipeline

    cfg = _checked_config(doc)
    shapes = jax.eval_shape(lambda: pipeline.init_states(cfg))
    states = jax.tree_util.tree_map_with_path(
        lambda path, s: jnp.asarray(
            leaves[jax.tree_util.keystr(path).lstrip(".")], s.dtype), shapes)
    d = tempfile.mkdtemp(prefix="bench-warm-")
    try:
        pipeline.save_stream_checkpoint(d, events_processed, states)
        del states
        return StreamSession.restore(d, cfg, publish=_policy(publish))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def host_states(states) -> dict:
    """The state pytree as ``{"tables.user_ids": np.ndarray, ...}``."""
    import jax
    import numpy as np

    return {jax.tree_util.keystr(path).lstrip("."): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(states)}
