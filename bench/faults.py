"""Faults planted under the timed path, to show that the check fails
them.  Each takes the warmed server and breaks it in place.

- ``altered_token``: every third decoded token is replaced where the
  server hands it to the scheduler.
- ``stale_selection``: no GRIFFIN slot buffer is written after warm-up,
  so every request decodes with the neurons a warm-up request chose.
- ``foreign_selection``: each request's compacted neurons are written
  into the next slot, so a request decodes with the neurons that
  another request's prompt chose.
"""
from __future__ import annotations


def altered_token(srv) -> None:
    orig = srv.sched.finish_decode_token
    count = {"n": 0}

    def finish(req, token):
        count["n"] += 1
        if count["n"] % 3 == 0:
            token = (token + 1) % srv.cfg.vocab_size
        return orig(req, token)

    srv.sched.finish_decode_token = finish


def stale_selection(srv) -> None:
    orig = srv._install_pruned

    def install(slot, tree):
        if srv.pruned_slots is None:
            orig(slot, tree)

    srv._install_pruned = install


def foreign_selection(srv) -> None:
    orig = srv._install_pruned

    def install(slot, tree):
        orig((slot + 1) % srv.n_slots, tree)

    srv._install_pruned = install


FAULTS = {f.__name__: f for f in (altered_token, stale_selection,
                                  foreign_selection)}
