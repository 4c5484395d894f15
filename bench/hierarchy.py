"""The deployment's hierarchy, built from its configuration alone.

The reference replays exchanges over the tables built here, never over
the program's plan.  Everything follows from the configuration (n, c,
graph_seed, k, a, cell_max, plan_seed, rep_mode) by the paper's rules
(§II–III, Alg. 1), with these conventions fixed where the paper leaves
a choice open:

* nodes: `default_rng(graph_seed).uniform(0, 1, (n, 2))`; radio radius
  r = sqrt(c ln n / n); u and v are neighbours where |u - v|^2 <= r^2;
* a node's neighbour order (the order a uniform neighbour draw indexes):
  grouped by the offset of the neighbour's bucket from the node's own
  on a grid of side int(1/r), offsets row-major (dy, then dx, each
  -1, 0, 1), ascending node id within a bucket;
* partition: S_1 = 1, S_{j+1} = S_j * max(2, round(q^((1-a)/2))) with
  q = n / S_j^2, k the smallest with n^(a^(k-1)) <= cell_max; cells are
  row-major on the S_j x S_j grid;
* finest level: one graph per occupied cell, cells ascending, slots its
  nodes ascending, each row its in-cell neighbours in neighbour order;
* election (`rep_mode="random"`): one `integers(size)` per graph from
  `default_rng(plan_seed)`, finest level first, then every overlay
  level but the top;
* overlay level j: one graph per level-j cell with occupied children,
  slots its children's representatives in ascending cell order; edges
  between N/S/E/W adjacent children, horizontal pairs first, each run
  row-major; while a grid is disconnected, the component whose
  union-find root is smallest (edges merged in order, the first
  endpoint's root under the second's) is joined to the rest by its
  nearest pair of members; each edge (u, v) appends v to u's row, then
  u to v's row;
* routes: greedy geographic from one representative towards the
  other's position, moving to the neighbour nearest the target (the
  first in neighbour order on ties) while that is nearer than the
  current node; where it stops short, the FIFO breadth-first shortest
  path in neighbour order.  One use of an overlay edge costs
  2 max(1, hops) transmissions: each endpoint sends once, each interior
  node of the route twice;
* promotion: a graph's representative value times its node count
  (mass-weighted) or Alg. 1 line 16's factor (finest level, otherwise
  1) goes to its slot in the parent's grid; every node reads its
  level-2 cell's slot of the top grid, and the down-pass costs one
  message per node.
"""
from __future__ import annotations

import itertools
import math
from collections import deque

import numpy as np

from .reference import Hierarchy, Level

__all__ = ["Field", "build", "levels_of"]

_OFFSETS = [(dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)]
_ROUTE_CHUNK = 4096


def levels_of(n: int, k, a: float, cell_max: float) -> list:
    """Grid sides S_1 .. S_k of the multiscale partition."""
    if k is None:
        if n <= cell_max:
            k = 1
        else:
            k = max(2, 1 + math.ceil(
                math.log(math.log(cell_max) / math.log(n)) / math.log(a)))
    sides = [1]
    while len(sides) < k:
        q = n / sides[-1] ** 2
        sides.append(sides[-1] * max(2, round(q ** ((1.0 - a) / 2.0))))
    return sides


def cell_of(xy: np.ndarray, side: int) -> np.ndarray:
    """Row-major cell of each position on a side x side grid."""
    ij = np.minimum((xy * side).astype(np.int64), side - 1)
    return ij[:, 1] * side + ij[:, 0]


def cell_of_parent(cells: np.ndarray, side: int, pside: int) -> np.ndarray:
    """Parent cell (pside x pside grid) of each cell of a side x side
    grid."""
    f = side // pside
    return (cells // side // f) * pside + (cells % side) // f


class Field:
    """Nodes in the unit square and their radio neighbourhoods, found
    through a bucket grid of side int(1/r) (no adjacency is stored)."""

    def __init__(self, n: int, c: float, seed: int):
        self.n = n
        self.xy = np.random.default_rng(seed).uniform(0.0, 1.0, (n, 2))
        self.r = float(np.sqrt(c * np.log(n) / n))
        self.m = max(1, int(1.0 / self.r))
        b = np.clip((self.xy * self.m).astype(np.int64), 0, self.m - 1)
        self.bx, self.by = b[:, 0], b[:, 1]
        bucket = self.by * self.m + self.bx
        self.order = np.argsort(bucket, kind="stable")
        self.count = np.bincount(bucket, minlength=self.m * self.m)
        self.first = np.concatenate([[0], np.cumsum(self.count)[:-1]])
        self.xs = np.ascontiguousarray(self.xy[self.order, 0])
        self.ys = np.ascontiguousarray(self.xy[self.order, 1])

    def offset(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """Row-major index (0..8) of v's bucket offset from u's."""
        return (self.by[v] - self.by[u] + 1) * 3 + (self.bx[v] - self.bx[u]
                                                    + 1)

    def _near(self, u: np.ndarray, target=None) -> tuple:
        """(owner index, position in bucket order, rank in the owner's
        neighbour order) of every neighbour of every node in `u`, in no
        particular order.  With `target` = (x, y, d2) per node, only
        buckets that may hold a point nearer than sqrt(d2) to (x, y)."""
        owners, where, offs = [], [], []
        for o, (dy, dx) in enumerate(_OFFSETS):
            by, bx = self.by[u] + dy, self.bx[u] + dx
            ok = (by >= 0) & (by < self.m) & (bx >= 0) & (bx < self.m)
            if target is not None:
                tx, ty, d2 = target
                gx = np.maximum(np.maximum(bx / self.m - tx,
                                           tx - (bx + 1) / self.m), 0.0)
                gy = np.maximum(np.maximum(by / self.m - ty,
                                           ty - (by + 1) / self.m), 0.0)
                ok &= gx * gx + gy * gy < d2 * (1.0 + 1e-9)
            own = np.nonzero(ok)[0]
            b = by[own] * self.m + bx[own]
            cnt = self.count[b]
            rep = np.repeat(np.arange(len(own)), cnt)
            owners.append(own[rep])
            where.append(np.arange(len(rep)) + np.repeat(
                self.first[b] - (np.cumsum(cnt) - cnt), cnt))
            offs.append(np.full(len(rep), o))
        own, pos, off = (np.concatenate(a) for a in (owners, where, offs))
        dx = self.xs[pos] - self.xy[u, 0][own]
        dy = self.ys[pos] - self.xy[u, 1][own]
        v = self.order[pos]
        keep = (dx * dx + dy * dy <= self.r * self.r) & (v != u[own])
        own, pos, v = own[keep], pos[keep], v[keep]
        return own, pos, off[keep] * self.n + v

    def neighbours(self, u: int) -> np.ndarray:
        """The neighbours of node u, in neighbour order."""
        _, pos, rank = self._near(np.array([u]))
        return self.order[pos[np.argsort(rank)]]

    def routes(self, src: np.ndarray, dst: np.ndarray) -> list:
        """The route (node ids, source first) of every (src, dst) pair."""
        paths = []
        for lo in range(0, len(src), _ROUTE_CHUNK):
            paths += self._greedy(src[lo:lo + _ROUTE_CHUNK],
                                  dst[lo:lo + _ROUTE_CHUNK])
        for e, p in enumerate(paths):
            if p[-1] != dst[e]:
                p_bfs = self._bfs(int(src[e]), int(dst[e]))
                if p_bfs is not None:
                    paths[e] = p_bfs
        return paths

    def _greedy(self, src: np.ndarray, dst: np.ndarray) -> list:
        tx, ty = self.xy[dst, 0], self.xy[dst, 1]
        cur = src.copy()
        d_cur = (self.xy[cur, 0] - tx) ** 2 + (self.xy[cur, 1] - ty) ** 2
        paths = [[int(s)] for s in src]
        live = np.arange(len(src))
        while len(live):
            own, pos, rank = self._near(
                cur[live], (tx[live], ty[live], d_cur[live]))
            d = (self.xs[pos] - tx[live][own]) ** 2 + \
                (self.ys[pos] - ty[live][own]) ** 2
            best = np.full(len(live), np.inf)
            np.minimum.at(best, own, d)
            # the nearest, and the first in neighbour order on ties
            tie = d == best[own]
            first = np.full(len(live), np.iinfo(np.int64).max)
            np.minimum.at(first, own[tie], rank[tie])
            moves = best < d_cur[live]
            who, nxt = live[moves], first[moves] % self.n
            cur[who], d_cur[who] = nxt, best[moves]
            for e, w in zip(who.tolist(), nxt.tolist()):
                paths[e].append(w)
            live = who[d_cur[who] > 0]      # none is nearer than the target
        return paths

    def _bfs(self, src: int, dst: int):
        prev = {src: src}
        q = deque([src])
        while q:
            u = q.popleft()
            if u == dst:
                break
            for v in self.neighbours(u).tolist():
                if v not in prev:
                    prev[v] = u
                    q.append(v)
        if dst not in prev:
            return None
        path = [dst]
        while path[-1] != src:
            path.append(prev[path[-1]])
        return path[::-1]


def _elect(rng, sizes: np.ndarray) -> np.ndarray:
    """Representative slot of each graph, drawn uniformly: the same
    draws, in order, as one `rng.integers(size)` per graph."""
    return rng.integers(sizes).astype(np.int64)


def _group(keys: np.ndarray) -> tuple:
    """Graph index and slot of each element, grouped by key ascending,
    elements in their given order within a group."""
    uniq, graph = np.unique(keys, return_inverse=True)
    order = np.argsort(graph, kind="stable")
    counts = np.bincount(graph)
    slot = np.empty(len(keys), np.int64)
    slot[order] = np.arange(len(keys)) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    return uniq, graph, slot, counts


def _csr(B: int, C: int, row_graph, row_slot, col) -> tuple:
    """(start (B, C), flat neighbour slots + a trailing pad entry, the
    entry order) of directed entries given in row order per row."""
    rowid = row_graph * C + row_slot
    srt = np.argsort(rowid, kind="stable")
    degrees = np.bincount(rowid, minlength=B * C).reshape(B, C)
    start = np.concatenate([[0], np.cumsum(degrees.ravel())[:-1]]) \
        .reshape(B, C)
    nbr = np.concatenate([col[srt], [0]])
    pos = np.empty(len(rowid), np.int64)
    pos[srt] = np.arange(len(rowid))
    return start, degrees, nbr, pos


def _components(num: int, edges: list) -> np.ndarray:
    """Union-find root of each member, edges merged in order with the
    first endpoint's root put under the second's."""
    parent = list(range(num))

    def find(u):
        while parent[u] != u:
            u = parent[u]
        return u

    for u, v in edges:
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[ru] = rv
    return np.array([find(u) for u in range(num)])


def _connect(edges: list, xy: np.ndarray) -> list:
    """`edges` of one grid, with nearest-pair edges appended until the
    grid (slot positions `xy`) is connected."""
    num = len(xy)
    while num > 1:
        root = _components(num, edges)
        if (root == root[0]).all():
            break
        a = np.nonzero(root == root.min())[0]
        b = np.nonzero(root != root.min())[0]
        d = ((xy[a][:, None, :] - xy[b][None, :, :]) ** 2).sum(axis=2)
        ia, ib = np.unravel_index(int(np.argmin(d)), d.shape)
        edges = edges + [(int(a[ia]), int(b[ib]))]
    return edges


def _connect_grids(graph, slot, ge, sizes, xy) -> tuple:
    """(grid, slot, slot) of every edge of the level, grouped by grid,
    with nearest-pair edges appended to each grid that is disconnected.
    `graph`, `slot`: each member's grid and slot; `ge`: the edges as member
    pairs, grouped by grid; `xy`: (G, C, 2) slot positions."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    num = len(graph)
    adj = sp.coo_matrix((np.ones(len(ge)), (ge[:, 0], ge[:, 1])),
                        shape=(num, num))
    label = connected_components(adj, directed=False)[1]
    parts = np.unique(graph * num + label)
    e_g, e_u, e_v = graph[ge[:, 0]], slot[ge[:, 0]], slot[ge[:, 1]]
    bad = np.nonzero(np.bincount(parts // num, minlength=len(sizes)) > 1)[0]
    if not len(bad):
        return e_g, e_u, e_v
    lo = np.searchsorted(e_g, bad)
    hi = np.searchsorted(e_g, bad, side="right")
    add = []
    for g, s0, s1 in zip(bad.tolist(), lo.tolist(), hi.tolist()):
        base = list(zip(e_u[s0:s1].tolist(), e_v[s0:s1].tolist()))
        full = _connect(base, xy[g, :sizes[g]])
        add += [(g, u, v) for u, v in full[len(base):]]
    add = np.array(add, np.int64).reshape(-1, 3)
    g_all = np.concatenate([e_g, add[:, 0]])
    order = np.lexsort((np.arange(len(g_all)), g_all))
    return (g_all[order], np.concatenate([e_u, add[:, 1]])[order],
            np.concatenate([e_v, add[:, 2]])[order])


def _grid_edges(side: int, f: int) -> np.ndarray:
    """N/S/E/W pairs of level cells on a side x side grid that share a
    parent (blocks of f x f): horizontal pairs, then vertical."""
    idx = np.arange(side * side).reshape(side, side)
    inner = (np.arange(side - 1) + 1) % f != 0
    horiz = np.stack([idx[:, :-1][:, inner].ravel(),
                      idx[:, 1:][:, inner].ravel()], 1)
    vert = np.stack([idx[:-1, :][inner, :].ravel(),
                     idx[1:, :][inner, :].ravel()], 1)
    return np.concatenate([horiz, vert])


def build(cfg: dict) -> Hierarchy:
    """The hierarchy of the deployment `cfg` (a configuration file's
    contents), by the conventions in the module's docstring."""
    n = int(cfg["n"])
    field = Field(n, float(cfg["c"]), int(cfg["graph_seed"]))
    sides = levels_of(n, cfg["k"], float(cfg["a"]), float(cfg["cell_max"]))
    K = len(sides)
    if K < 2:
        raise ValueError("a hierarchy of one level is not a deployment "
                         "the benchmark runs")
    if cfg["rep_mode"] != "random":
        raise ValueError(f"rep_mode {cfg['rep_mode']!r}: the benchmark's "
                         "deployments elect at random")
    rng = np.random.default_rng(int(cfg["plan_seed"]))
    xy = field.xy

    # finest level: the occupied cells' induced subgraphs
    cell = cell_of(xy, sides[-1])
    cells, graph, slot, sizes = _group(cell)
    B, C = len(cells), int(sizes.max())
    slot_node = np.full((B, C), -1, np.int64)
    slot_node[graph, slot] = np.arange(n)
    # every ordered pair of nodes of one cell, then those in radio range
    by_cell = np.argsort(cell, kind="stable")
    q = sizes[graph]
    src = np.repeat(np.arange(n), q)
    within = np.arange(len(src)) - np.repeat(np.cumsum(q) - q, q)
    dst = by_cell[(np.cumsum(sizes) - sizes)[graph[src]] + within]
    d = xy[dst] - xy[src]
    keep = (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1] <= field.r * field.r) \
        & (src != dst)
    src, dst = src[keep], dst[keep]
    srt = np.lexsort((dst, field.offset(src, dst), src))
    src, dst = src[srt], dst[srt]
    # rows in (graph, slot) order, each in neighbour order
    start, degrees, nbr, pos = _csr(B, C, graph[src], slot[src], slot[dst])
    row_node = np.full(len(nbr), n)
    partner = np.full(len(nbr), n)
    row_node[pos], partner[pos] = src, dst
    rep_slot = _elect(rng, sizes)
    parents = cell_of_parent(cells, sides[-1], sides[-2])
    _, pg, _, pcount = _group(parents)
    pop = np.bincount(pg, weights=sizes.astype(np.float64))
    line16 = (sizes * pcount[pg].astype(np.float64) / pop[pg]).astype(
        np.float32)
    levels = [dict(kind="cells", start=start, nbr=nbr,
                   hops=np.ones(len(nbr), np.int64), degrees=degrees,
                   n_nodes=sizes, slot_node=slot_node, row_node=row_node,
                   partner_flat=partner, rep_slot=rep_slot, line16=line16)]
    rep_node = slot_node[np.arange(B), rep_slot]
    cur = cells

    # overlay levels k-1 .. 1: grids of the children's representatives
    for j in range(K - 1, 0, -1):
        side, pside = sides[j], sides[j - 1]
        parents = cell_of_parent(cur, side, pside)
        pcells, graph, slot, sizes = _group(parents)
        levels[-1].update(next_graph=graph, next_slot=slot)
        G, Cg = len(pcells), int(sizes.max())
        slot_node = np.full((G, Cg), -1, np.int64)
        slot_node[graph, slot] = rep_node
        where = np.full(side * side, -1)
        where[cur] = np.arange(len(cur))
        ge = where[_grid_edges(side, side // pside)]
        ge = ge[(ge >= 0).all(axis=1)]
        ge = ge[np.argsort(graph[ge[:, 0]], kind="stable")]
        e_g, e_u, e_v = _connect_grids(graph, slot, ge, sizes,
                                       xy[slot_node])
        paths = field.routes(slot_node[e_g, e_u], slot_node[e_g, e_v])
        L = np.array([len(p) for p in paths])
        flat = np.fromiter(itertools.chain.from_iterable(paths), np.int64,
                           count=int(L.sum()))
        at = np.arange(len(flat)) - np.repeat(np.cumsum(L) - L, L)
        on = np.repeat(L > 1, L)
        start, degrees, nbr, pos = _csr(
            G, Cg, np.repeat(e_g, 2), np.stack([e_u, e_v], 1).ravel(),
            np.stack([e_v, e_u], 1).ravel())
        hops = np.ones(len(nbr), np.int64)
        hops[pos] = np.repeat(np.maximum(1, L - 1), 2)
        lv = dict(kind="overlay", start=start, nbr=nbr, hops=hops,
                  degrees=degrees, n_nodes=sizes, slot_node=slot_node,
                  edge_pos_i=pos[0::2], edge_pos_j=pos[1::2],
                  inc_node=flat[on],
                  inc_edge=np.repeat(np.arange(len(paths)), L)[on],
                  inc_count=np.where(
                      (at == 0) | (at == np.repeat(L - 1, L)), 1, 2)[on])
        levels.append(lv)
        if j > 1:
            rep_slot = _elect(rng, sizes)
            lv.update(rep_slot=rep_slot, line16=np.ones(G, np.float32))
            rep_node = slot_node[np.arange(G), rep_slot]
            cur = pcells

    # every node reads its level-2 cell's slot in the top grid
    top_slot = np.full(sides[1] ** 2, -1)
    top_slot[cur] = np.arange(len(cur))
    final_slot = top_slot[cell_of(xy, sides[1])]
    return Hierarchy(
        n=n, levels=[_level(lv) for lv in levels],
        final_graph=np.zeros(n, np.int64), final_slot=final_slot,
        disseminate=True)


def _level(d: dict) -> Level:
    C = d["slot_node"].shape[1]
    d["node_mask"] = np.arange(C)[None, :] < d["n_nodes"][:, None]
    return Level(**d)
