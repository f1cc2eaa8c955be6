"""Seeded twins of the WIKI-Dir and ARXIV-Dir corpora and their streams.

A configuration fixes the corpus's shape: its directory trees (count, depth
profile), the number of entries and how they spread over directories, the
embedding width. That shape, and the pools the traffic draws from (query
templates and MOVE/MERGE ops), come from the configuration's own
``structure_seed``, so every ``--seed`` serves the same deployment with the
same set of queries and structural updates. ``--seed`` draws the vectors
and the query noise, and orders the query pool.

Everything here is vectorised NumPy except the tree walks that the update
stream needs (depth ~12, some thousands of ops); the vectors are drawn on the
device (:func:`device_vectors`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np


# ------------------------------------------------------------------ trees
@dataclass
class Tree:
    """A directory tree as parent pointers. Node 0 is the root ``/``;
    ``names[i]`` is node i's segment (globally unique, so a MOVE or MERGE
    never meets a name clash)."""
    parent: np.ndarray          # (D,) int64, -1 for the root
    names: List[str]
    depth: np.ndarray           # (D,) int64

    def __len__(self) -> int:
        return len(self.names)

    def paths(self) -> List[str]:
        """Canonical ``/a/b/`` path of every node (parents precede
        children in node order)."""
        out = ["/"] * len(self)
        for i in range(1, len(self)):
            out[i] = out[self.parent[i]] + self.names[i] + "/"
        return out


def build_tree(rng: np.random.Generator, n_dirs: int, avg_depth: float,
               depth_sd: float, prefix: str) -> Tree:
    """``n_dirs`` directories under a root. Depths are drawn from
    N(avg_depth, depth_sd), rounded and clipped to >= 1, and cut at the
    first empty level so every level has a parent level; each directory
    hangs under a uniformly drawn directory of the level above. Nodes are
    numbered in depth order, so a parent always has a lower number."""
    d = np.clip(np.rint(rng.normal(avg_depth, depth_sd, n_dirs)), 1,
                None).astype(np.int64)
    counts = np.bincount(d)
    empty = np.flatnonzero(counts[1:] == 0)
    if len(empty):
        d = np.minimum(d, empty[0])          # levels 1..empty[0] are filled
    d = np.sort(d)
    depth = np.concatenate([[0], d])
    counts = np.bincount(depth)
    start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    lvl = depth[1:]
    parent = np.empty(n_dirs + 1, np.int64)
    parent[0] = -1
    parent[1:] = start[lvl - 1] + (rng.random(n_dirs)
                                   * counts[lvl - 1]).astype(np.int64)
    names = [""] + [f"{prefix}{i}" for i in range(1, n_dirs + 1)]
    return Tree(parent=parent, names=names, depth=depth)


def zipf_assign(rng: np.random.Generator, n_entries: int, n_nodes: int,
                a: float, every_dir: bool = False) -> np.ndarray:
    """Entry -> directory (1..n_nodes-1), Zipf(a) over a random ranking of
    the non-root directories. ``every_dir`` first gives each directory one
    entry and spreads the rest so; entries are then in a random order."""
    ranks = rng.permutation(n_nodes - 1)
    w = 1.0 / np.power(ranks + 1.0, a)
    if not every_dir:
        return 1 + rng.choice(n_nodes - 1, size=n_entries, p=w / w.sum())
    rest = 1 + rng.choice(n_nodes - 1, size=n_entries - (n_nodes - 1),
                          p=w / w.sum())
    return rng.permutation(np.concatenate([np.arange(1, n_nodes), rest]))


def live_nodes(tree: Tree, entry_dir: np.ndarray) -> np.ndarray:
    """Bool (D,): the occupied directories and all their ancestors — the
    directories a scope index built from the entries knows."""
    live = np.zeros(len(tree), bool)
    live[entry_dir] = True
    live[0] = True
    for i in range(len(tree) - 1, 0, -1):    # children after parents
        if live[i]:
            live[tree.parent[i]] = True
    return live


def subtree_counts(tree: Tree, entry_dir: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(direct, recursive) entry counts per node."""
    direct = np.bincount(entry_dir, minlength=len(tree))
    rec = direct.copy()
    for i in range(len(tree) - 1, 0, -1):
        rec[tree.parent[i]] += rec[i]
    return direct, rec


def top_branch(tree: Tree) -> np.ndarray:
    """(D,) index of each node's depth-1 ancestor among the depth-1 nodes
    (the root maps to 0)."""
    tops = np.flatnonzero(tree.depth == 1)
    rank = np.zeros(len(tree), np.int64)
    rank[tops] = np.arange(len(tops))
    out = np.zeros(len(tree), np.int64)
    for i in range(1, len(tree)):
        out[i] = rank[i] if tree.depth[i] == 1 else out[tree.parent[i]]
    return out


# ----------------------------------------------------------------- corpus
@dataclass
class Namespace:
    name: str
    tree: Tree
    entry_dir: np.ndarray       # (N,) node of each entry

    def entry_paths(self) -> List[str]:
        paths = self.tree.paths()
        return [paths[d] for d in self.entry_dir]


@dataclass
class Corpus:
    """The deployment's fixed shape: its namespaces and the branch each
    entry's vector clusters around."""
    namespaces: Dict[str, Namespace]
    query_ns: str
    n_entries: int
    dim: int
    n_branches: int
    branch: np.ndarray          # (N,) int32 cluster of each entry
    branch_noise: float
    query_noise: float

    @property
    def primary(self) -> Namespace:
        return self.namespaces[self.query_ns]


def build_corpus(cfg: dict) -> Corpus:
    """The corpus of configuration ``cfg`` (see ``bench/configs``)."""
    rng = np.random.default_rng(cfg["structure_seed"])
    n = int(cfg["entries"])
    spaces: Dict[str, Namespace] = {}
    for ns in cfg["namespaces"]:
        tree = build_tree(rng, int(ns["dirs"]), float(ns["avg_depth"]),
                          float(ns["depth_sd"]), ns["prefix"])
        entry_dir = zipf_assign(rng, n, len(tree), float(ns["entry_zipf"]),
                                bool(ns.get("every_dir_occupied", False)))
        spaces[ns["name"]] = Namespace(ns["name"], tree, entry_dir)
    primary = spaces[cfg["query_namespace"]]
    branch = top_branch(primary.tree)[primary.entry_dir].astype(np.int32)
    return Corpus(namespaces=spaces, query_ns=cfg["query_namespace"],
                  n_entries=n, dim=int(cfg["dim"]),
                  n_branches=int(np.count_nonzero(primary.tree.depth == 1)),
                  branch=branch,
                  branch_noise=float(cfg["vectors"]["branch_noise"]),
                  query_noise=float(cfg["vectors"]["query_noise"]))


def jax_key(seed: int):
    """A JAX PRNG key from any whole number (64 bits and beyond)."""
    import jax
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


BLOCK_ROWS = 32768


def _vector_block_fn(dim: int, n_branches: int, noise: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(key, b, branch):
        """Rows of block ``b``: unit vectors around their branch's unit
        centre (the same centres for every block of one key)."""
        ck, nk = jax.random.split(key)
        centres = jax.random.normal(ck, (n_branches, dim), jnp.float32)
        centres = centres / jnp.linalg.norm(centres, axis=1, keepdims=True)
        eps = jax.random.normal(jax.random.fold_in(nk, b),
                                (branch.shape[0], dim), jnp.float32)
        v = centres[branch] + noise * eps
        return v / jnp.linalg.norm(v, axis=1, keepdims=True)
    return block


def device_vectors(corpus: Corpus, seed: int) -> np.ndarray:
    """(N, dim) float32 entry vectors drawn on the device from ``seed``,
    block by block (one compiled program), returned as one host array. The
    device holds one block at a time, so the draw never sets the process's
    device-memory peak."""
    import jax
    import jax.numpy as jnp
    fn = _vector_block_fn(corpus.dim, corpus.n_branches, corpus.branch_noise)
    key = jax.random.fold_in(jax_key(seed), 0)
    n = corpus.n_entries
    out = np.empty((n, corpus.dim), np.float32)
    for b, lo in enumerate(range(0, n, BLOCK_ROWS)):
        hi = min(lo + BLOCK_ROWS, n)
        br = np.zeros(BLOCK_ROWS, np.int32)
        br[: hi - lo] = corpus.branch[lo:hi]
        out[lo:hi] = np.asarray(fn(key, b, jnp.asarray(br)))[: hi - lo]
    return out


def query_vectors(corpus: Corpus, vectors: np.ndarray, entries: np.ndarray,
                  seed: int) -> np.ndarray:
    """(len(entries), dim) unit query vectors: each entry's vector plus
    ``query_noise`` Gaussian noise, drawn on the device from ``seed``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key, rows):
        v = rows + corpus.query_noise * jax.random.normal(
            key, rows.shape, jnp.float32)
        return v / jnp.linalg.norm(v, axis=1, keepdims=True)
    key = jax.random.fold_in(jax_key(seed), 1)
    return np.asarray(draw(key, jnp.asarray(vectors[entries])))


# ----------------------------------------------------------------- traffic
@dataclass
class QueryTemplate:
    """Pool of queries: entry ``entry[i]`` anchors query i at the
    ancestor ``floor(level[i] * (depth + 1))`` levels below the root of
    the entry's directory as the tree then stands; ``recursive[i]`` scopes
    the whole subtree."""
    entry: np.ndarray           # (M,) int64
    level: np.ndarray           # (M,) float in [0, 1)
    recursive: np.ndarray       # (M,) bool

    def __len__(self) -> int:
        return len(self.entry)

    def take(self, order: np.ndarray) -> "QueryTemplate":
        return QueryTemplate(self.entry[order], self.level[order],
                             self.recursive[order])


def query_pool(corpus: Corpus, traffic: dict, size: int,
               structure_seed: int) -> QueryTemplate:
    """``size`` query templates from the configuration's structure seed.
    ``anchor_zipf`` 0 draws entries uniformly; above 0 it draws an occupied
    directory with Zipf(anchor_zipf) weight over a random ranking, then a
    uniform entry of it."""
    rng = np.random.default_rng([structure_seed, 3])
    entry_dir = corpus.primary.entry_dir
    a = float(traffic["anchor_zipf"])
    if a <= 0:
        entry = rng.integers(corpus.n_entries, size=size)
    else:
        order = np.argsort(entry_dir, kind="stable")
        occupied, first, count = np.unique(entry_dir[order],
                                           return_index=True,
                                           return_counts=True)
        ranks = rng.permutation(len(occupied))
        w = 1.0 / np.power(ranks + 1.0, a)
        pick = rng.choice(len(occupied), size=size, p=w / w.sum())
        within = (rng.random(size) * count[pick]).astype(np.int64)
        entry = order[first[pick] + within]
    level = rng.random(size)
    recursive = rng.random(size) < float(traffic["recursive_share"])
    return QueryTemplate(entry.astype(np.int64), level, recursive)


# ---------------------------------------------------------- live tree, DSM
class LiveTree:
    """The primary namespace as it stands under a stream of MOVE and
    MERGE ops: parent pointers over the original node numbers, a live
    flag per node, and each entry's directory. A MOVE re-parents a
    directory under one of its parent's level and a MERGE joins two
    directories of one level, so every directory keeps its depth."""

    def __init__(self, ns: Namespace):
        t = ns.tree
        self.names = t.names
        self.parent = t.parent.copy()
        self.depth = t.depth
        self.alive = live_nodes(t, ns.entry_dir)
        self.entry_dir = ns.entry_dir.copy()
        self.levels: List[List[int]] = [[] for _ in range(
            int(t.depth.max()) + 1)]
        for i in np.flatnonzero(self.alive[1:]) + 1:   # non-root live
            self.levels[t.depth[i]].append(int(i))

    def chain(self, node: int) -> List[int]:
        """Root-first list of ``node``'s ancestors and itself."""
        out = []
        while node >= 0:
            out.append(node)
            node = int(self.parent[node])
        return out[::-1]

    def path(self, node: int) -> str:
        segs = [self.names[i] for i in self.chain(node)[1:]]
        return "/" + "".join(s + "/" for s in segs) if segs else "/"

    def is_ancestor_or_self(self, a: int, b: int) -> bool:
        while b >= 0:
            if b == a:
                return True
            b = int(self.parent[b])
        return False

    def anchor(self, entry: int, level: float) -> str:
        chain = self.chain(int(self.entry_dir[entry]))
        return self.path(chain[int(level * len(chain))])

    def move(self, src: int, new_parent: int) -> None:
        self.parent[src] = new_parent

    def merge(self, src: int, dst: int) -> None:
        kids = np.flatnonzero(self.parent == src)
        self.parent[kids] = dst
        self.parent[src] = -1
        self.alive[src] = False
        self.entry_dir[self.entry_dir == src] = dst
        self.levels[self.depth[src]].remove(src)

    def draw_pair(self, rng: np.random.Generator, kind: str, shallow: bool,
                  shallow_depth: int) -> Tuple[int, int]:
        """(src, dst) for a MOVE (dst a directory of src's parent's level,
        not its parent) or a MERGE (dst another directory of src's level):
        live, non-root, neither an ancestor of the other. ``shallow``
        draws src within ``shallow_depth`` levels of the root (large
        subtrees), else src is uniform over the live directories."""
        up = 1 if kind == "move" else 0          # dst's level above src's
        first = 1 + up
        last = shallow_depth if shallow else len(self.levels) - 1
        # levels whose sources have somewhere to go
        sizes = np.asarray([len(self.levels[d]) * (len(self.levels[d - up])
                                                   > 1)
                            for d in range(first, last + 1)])
        if not sizes.sum() and shallow:
            return self.draw_pair(rng, kind, False, shallow_depth)
        for _ in range(1000 if sizes.sum() else 0):
            r = int(rng.integers(sizes.sum()))
            d = first + int(np.searchsorted(np.cumsum(sizes), r, "right"))
            src = self.levels[d][r - int(sizes[: d - first].sum())]
            to = self.levels[d - up]
            dst = to[int(rng.integers(len(to)))]
            if dst in (src, int(self.parent[src])):
                continue
            if (self.is_ancestor_or_self(src, dst)
                    or self.is_ancestor_or_self(dst, src)):
                continue
            return src, dst
        raise RuntimeError("no disjoint pair of live directories")


@dataclass
class DsmOp:
    """One structural update, by node number (for the generator's own
    live tree) and by path (as the program and the reference take it)."""
    kind: str
    src: int
    dst: int
    src_path: str
    dst_path: str

    def apply(self, live: LiveTree) -> None:
        (live.move if self.kind == "move" else live.merge)(self.src, self.dst)


def draw_ops(ns: Namespace, traffic: dict, n: int,
             structure_seed: int) -> List[DsmOp]:
    """``n`` MOVE/MERGE ops, each drawn against the live tree as the ops
    before it left it (:meth:`LiveTree.draw_pair`): MOVE and MERGE
    alternate, sources alternate between shallow and uniform. Queries leave the tree as it is, so the sequence
    depends only on the structure seed: every ``--seed`` gets the same ops
    in the same order."""
    rng = np.random.default_rng([int(structure_seed), 11])
    live = LiveTree(ns)
    kinds = ["move", "merge"]
    flip = int(rng.integers(2))
    shallow_depth = int(traffic.get("shallow_depth", 3))
    ops: List[DsmOp] = []
    for j in range(n):
        kind = kinds[(j + flip) % 2]
        src, dst = live.draw_pair(rng, kind, shallow=(j // 2) % 2 == 0,
                                  shallow_depth=shallow_depth)
        op = DsmOp(kind, src, dst, live.path(src), live.path(dst))
        ops.append(op)
        op.apply(live)
    return ops


class Anchors:
    """Anchors of fixed query templates on the tree as the first ``n``
    ops of a sequence left it: a client keeps to its entry and level while
    MOVE and MERGE carry the entry's directory elsewhere."""

    def __init__(self, ns: Namespace, ops: Sequence[DsmOp]):
        self.live = LiveTree(ns)
        self.ops = ops
        self.applied = 0

    def at(self, n_applied: int, entry: int, level: float) -> str:
        while self.applied < n_applied:
            self.ops[self.applied].apply(self.live)
            self.applied += 1
        return self.live.anchor(entry, level)


def scope_sizes(ns: Namespace) -> List[Tuple[str, bool, int]]:
    """``(anchor, recursive, size)`` of every non-empty scope of the
    unchanged tree."""
    direct, rec = subtree_counts(ns.tree, ns.entry_dir)
    paths = ns.tree.paths()
    live = live_nodes(ns.tree, ns.entry_dir)
    out = []
    for i in np.flatnonzero(live):
        out.append((paths[i], True, int(rec[i])))
        if direct[i]:
            out.append((paths[i], False, int(direct[i])))
    return out
