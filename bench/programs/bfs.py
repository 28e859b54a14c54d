"""BFS under Graph500's rules: search keys, TEPS work and validation.

Plain NumPy over the benchmark's own edge list; nothing here comes from
the program.  A parent outside ``[0, n)`` means "not reached", which
covers the engine's unreached marker whatever its value.
"""

from __future__ import annotations

import numpy as np

ALGO = "bfs"


def params(traffic: dict) -> dict:
    """Engine parameters the source fixes: none beyond the root."""
    return {}


def search_keys(traffic: dict, cfg: dict, edges: np.ndarray,
                perm: np.ndarray) -> np.ndarray:
    """Graph500's search keys: ``traffic["search_keys"]`` distinct
    vertices drawn uniformly from the configuration's ``structure_seed``
    among the vertices of degree >= 1, not counting self-loops, of the
    generated structure; no other filter.  They are drawn in the
    generator's labels and relabelled by ``perm``, so every seed
    searches the same vertices in the same order."""
    n = len(perm)
    proper = edges[edges[:, 0] != edges[:, 1]]
    # edges carry the permuted labels: vertex v of the structure is perm[v]
    degree = np.bincount(proper.ravel(), minlength=n)[perm]
    rng = np.random.default_rng(cfg["structure_seed"])
    keys = rng.choice(np.flatnonzero(degree >= 1), traffic["search_keys"],
                      replace=False)
    return perm[keys]


def inputs(traffic: dict, cfg: dict, edges: np.ndarray,
           perm: np.ndarray) -> list[tuple]:
    """One launch per search key, in the order drawn; the first also
    warms up, and the window searches them from the first, as far as it
    reaches (cycling if it reaches past the last)."""
    keys = search_keys(traffic, cfg, edges, perm)
    return [(np.int32(k),) for k in [keys[0], *keys]]


def levels(edges: np.ndarray, n: int, root: int) -> np.ndarray:
    """Hop distance of every vertex from ``root`` over the undirected
    edges; -1 where unreachable."""
    u, v = edges[:, 0], edges[:, 1]
    dist = np.full(n, -1, np.int32)
    dist[root] = 0
    frontier = np.zeros(n, bool)
    frontier[root] = True
    level = 0
    while True:
        nxt = np.zeros(n, bool)
        nxt[v[frontier[u]]] = True
        nxt[u[frontier[v]]] = True
        nxt &= dist < 0
        if not nxt.any():
            return dist
        level += 1
        dist[nxt] = level
        frontier = nxt


def tree_errors(edges: np.ndarray, n: int, root: int, parents: np.ndarray,
                dist: np.ndarray) -> int:
    """Vertices at which ``parents`` breaks Graph500's validation against
    the reference levels ``dist``: reached set differs, the root is not
    its own parent, a parent is not one level up, or a (parent, child)
    pair is not an edge."""
    parents = np.asarray(parents).astype(np.int64)
    reached = (parents >= 0) & (parents < n)
    bad = reached != (dist >= 0)
    bad[root] |= parents[root] != root
    child = reached & (dist >= 0)
    child[root] = False
    p = np.where(child, parents, 0)
    bad |= child & (dist[p] != dist - 1)
    u, v = edges[:, 0], edges[:, 1]
    vouched = np.zeros(n, bool)
    vouched[v[parents[v] == u]] = True
    vouched[u[parents[u] == v]] = True
    bad |= child & ~vouched
    return int(bad.sum())


def work(edges: np.ndarray, dist: np.ndarray) -> int:
    """Graph500's TEPS numerator: input edges (self-loops and duplicates
    included) within the searched component."""
    return int((dist[edges[:, 0]] >= 0).sum())


def check(traffic: dict, edges: np.ndarray, n: int,
          launches: list[dict]) -> dict:
    """Validate every search of the window; count its work."""
    errors, failed, works = 0, 0, []
    for rec in launches:
        root = int(rec["inputs"][0])
        dist = levels(edges, n, root)
        e = tree_errors(edges, n, root, rec["outputs"]["parents"], dist)
        errors += e
        failed += e > 0
        works.append(work(edges, dist))
    return {"failed": failed, "work": works,
            "checks": {"bfs_tree_errors": (errors, 0)}}


def control(traffic: dict, edges: np.ndarray, n: int,
            launches: list[dict]) -> list[dict]:
    """The reference with the BFS guarantee broken, in the program's
    place: a stale frontier, where a frontier vertex of the wrong parity
    waits a round before it expands, so a vertex can be claimed by a
    parent more than one level up.  Parent = least discovering id."""
    u = np.concatenate([edges[:, 0], edges[:, 1]])
    v = np.concatenate([edges[:, 1], edges[:, 0]])
    ids = np.arange(n)
    out = []
    for rec in launches:
        root = int(rec["inputs"][0])
        parents = np.full(n, -1, np.int64)
        parents[root] = root
        pending = np.zeros(n, bool)
        pending[root] = True
        r = 0
        while pending.any():
            active = pending & ((ids + r) % 2 == 0)
            if not active.any():
                active = pending.copy()
            pending &= ~active
            hit = active[u] & (parents[v] < 0)
            prop = np.full(n, n, np.int64)
            np.minimum.at(prop, v[hit], u[hit])
            new = prop < n
            parents[new] = prop[new]
            pending |= new
            r += 1
        out.append({**rec, "outputs": {"parents": parents}})
    return out
