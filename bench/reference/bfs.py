"""Plain numpy breadth-first search: the reference for the ``bfs`` traffic.

It follows the program's definition (GraphD paper, section 6: BFS is
SSSP over unit weights) and LDBC Graphalytics' BFS, in float64, and
imports nothing of the program. The search starts at the vertex index
``traffic["source_index"]``, which the harness draws from the traffic's
own seed (``bench/harness.py``, ``resolve_sources``):

    level(source) = 0
    level(v) = 1 + min over edges u -> v of level(u), inf where unreached

In superstep ``s`` the vertices at level ``s`` are active: each sends a
message along each of its out-edges, and the vertices it finds for the
first time take level ``s + 1``. After ``s`` supersteps every level up to
``s`` is known. The search halts after the superstep in which no vertex
is found: the superstep of the deepest level, so ``deepest level + 1``
supersteps in all.
"""

from __future__ import annotations

import numpy as np


def levels(src, dst, n: int, source: int) -> np.ndarray:
    """Each vertex's level from ``source`` (float64, inf where unreached),
    by vertex index."""
    lv = np.full(n, np.inf)
    lv[source] = 0.0
    frontier = np.zeros(n, bool)
    frontier[source] = True
    level = 0
    while frontier.any():
        found = dst[frontier[src]]
        found = np.unique(found[np.isinf(lv[found])])
        level += 1
        lv[found] = level
        frontier = np.zeros(n, bool)
        frontier[found] = True
    return lv


def supersteps(src, dst, n: int, traffic: dict) -> int:
    """Supersteps the search takes to its fixpoint: the deepest level + 1."""
    lv = levels(src, dst, n, int(traffic["source_index"]))
    return int(lv[np.isfinite(lv)].max()) + 1


def run(src, dst, n: int, traffic: dict, steps: int) -> np.ndarray:
    """Each vertex's value after ``steps`` supersteps, by vertex index
    (``src``/``dst`` index vertices ``0 .. n - 1``): its level where that
    is at most ``steps``, inf elsewhere."""
    lv = levels(src, dst, n, int(traffic["source_index"]))
    return np.where(lv <= steps, lv, np.inf)


def active_edges(src, dst, n: int, traffic: dict, steps: int) -> list[int]:
    """Edges out of the active vertices of each of ``steps`` supersteps:
    in superstep ``s``, the out-edges of the vertices at level ``s``."""
    lv = levels(src, dst, n, int(traffic["source_index"]))
    deg = np.bincount(src, minlength=n)
    return [int(deg[lv == s].sum()) for s in range(steps)]


def compare(got: np.ndarray, want: np.ndarray, traffic: dict) -> dict:
    """The numbers the check holds to the traffic's ``limits``: how many
    vertices' levels differ from the reference's (inf equals inf; a vertex
    with no value, NaN, differs)."""
    return dict(mismatched_vertices=int(np.count_nonzero(~(got == want))))
