"""Broken versions of the served path, and the control, for the checks that
``correct`` can fail.

The faults wrap a live ``QuerySession`` through its public entries
(``query`` for the closed loop, ``finish`` for the frontend's slabs), so
the rest of a run drives the broken path as it would the sound one:

* ``altered``: one answer of every batch or slab is flipped where it is
  produced;
* ``half``: the second half of every batch or slab is left unanswered
  (read as "not reachable");
* ``misrouted``: a slab's answers are shifted by one request, so each
  ticket gets its neighbour's answers.

The control, ``stale``, is the reference put in the program's place with
one of the configuration's guarantees broken: it answers every batch or
slab from a stale copy of the graph that lacks ``STALE_DROP`` of its edges,
drawn from the seed, where the configuration promises answers over every
edge of the graph. The program still serves underneath, so the run keeps
its pace; only the answers it hands back are the control's.
"""
from __future__ import annotations

import numpy as np

from .reference import Reference


def _wrap(session, change) -> None:
    """Pass every batch's or slab's answers through ``change``."""
    query, finish = session.query, session.finish
    session.query = lambda s, t: change(query(s, t).copy())
    session.finish = lambda h: change(finish(h).copy())


def altered(session) -> None:
    def change(ans):
        if ans.size:
            ans[ans.size // 2] ^= True
        return ans
    _wrap(session, change)


def half(session) -> None:
    def change(ans):
        ans[ans.size - ans.size // 2:] = False
        return ans
    _wrap(session, change)


def misrouted(session, pairs_per_request: int) -> None:
    _wrap(session, lambda ans: np.roll(ans, pairs_per_request))


FAULTS = {"altered": altered, "half": half, "misrouted": misrouted}


def restore(session) -> None:
    """Back to the session's own methods."""
    for name in ("query", "stage", "finish"):
        session.__dict__.pop(name, None)


STALE_DROP = 0.05            # share of edges the control's stale graph lacks


def stale_reference(n: int, src, dst, seed: int) -> Reference:
    """The control's graph: the configuration's less ``STALE_DROP`` of its
    edges, chosen by ``seed``."""
    src, dst = np.asarray(src), np.asarray(dst)
    keep = np.random.default_rng(seed + 11).random(src.size) >= STALE_DROP
    return Reference(n, src[keep], dst[keep])


def stale(session, reference: Reference) -> None:
    """Answer every batch or slab from ``reference`` in the session's
    place: ``query`` directly, a frontend slab by the pairs it staged."""
    query, stage, finish = session.query, session.stage, session.finish

    def staged(srcs, dsts):
        batch = stage(srcs, dsts)
        batch.pairs = (np.asarray(srcs), np.asarray(dsts))
        return batch

    def finished(handle):
        finish(handle)
        return reference.reachable(*handle.staged.pairs)

    session.query = lambda s, t: (query(s, t), reference.reachable(s, t))[1]
    session.stage = staged
    session.finish = finished
