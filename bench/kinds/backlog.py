"""Traffic kind ``backlog``: the trainer drains a full backlog.

A consumer catching up on a log: ``ingest`` calls of ``chunk_events``
events each, back to back, and no queries. Set-up makes the stream's
first periods, restores the session from the state after the
configuration's ``warm_periods`` of them where it asks for a warm start,
and warms up with one call (the only shape the window uses); the window
starts at the next call and ends when the call in flight at the
deadline returns.

Parameters (``traffic/<name>.json``): ``chunk_events``; ``ids``, how the
run's seed varies the stream (``streamgen``: ``"sampled"`` or
``"relabeled"``); and ``publish`` (``every`` micro-batches, ``mode``),
the session's publish policy.
"""

from __future__ import annotations

import time

import deploy
import streamgen


def run(h) -> None:
    import jax

    doc, chunk = h.doc, h.traffic["chunk_events"]
    feed = streamgen.PeriodFeed(
        h.profile, h.seed, ids=h.traffic.get("ids", "sampled"),
        classes=(doc["n_i"] + doc["w"], doc["n_i"]))
    h.marks["stream"] = time.perf_counter()
    if doc.get("warm_periods", 0):
        h.history = feed.take_periods(doc["warm_periods"])
        session = deploy.restored_session(doc, h.traffic["publish"],
                                          h.warm_state(), h.history[0].size)
    else:
        session = deploy.session(doc, h.traffic["publish"])
    h.session = session
    h.marks["session"] = time.perf_counter()
    mb = doc["micro_batch"]

    def ingest(users, items):
        res = session.ingest(users, items)
        jax.block_until_ready(session.states)
        h.calls.append((users, items))
        h.bits.append(res.recall.bits())
        h.forgets.append(res.forgets)
        return res

    ingest(*feed.take(chunk))
    h.marks["warm_up"] = time.perf_counter()
    feed.start()
    processed = offered = dropped = live = fresh = 0
    try:
        with h.window() as deadline:
            t0 = deadline - h.seconds
            first = len(h.calls)
            while True:
                users, items = feed.take(chunk)
                tc = time.perf_counter()
                res = ingest(users, items)
                n_live, n_fresh = len(res.load_history), -(-users.size // mb)
                h.call_done(s=time.perf_counter() - tc,
                            processed=res.events_processed,
                            live=n_live, fresh=n_fresh)
                offered += users.size
                processed += res.events_processed
                dropped += res.dropped
                live += n_live
                fresh += n_fresh
                if time.perf_counter() >= deadline:
                    break
            t1 = time.perf_counter()
    finally:
        feed.close()
    h.first_window_call = first
    h.processed, h.live_steps, h.fresh_steps = processed, live, fresh
    h.attempted, h.failed = offered, dropped
    h.starved_s = feed.starved_s
    h.e2e["events_per_s"] = processed / (t1 - t0)
