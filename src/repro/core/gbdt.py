"""Histogram-based gradient-boosted decision trees in pure JAX.

Stands in for the paper's LightGBM classifier (§III.C.1): multiclass
softmax objective, quantile-binned features (64 bins), depth-limited
level-order trees, class weights inversely proportional to frequency.

Everything is fixed-shape and jittable: the per-round tree build uses
segment-sum histograms over (node, feature, bin), vectorized split search,
and level-order node propagation.

Prediction traverses flattened *node tables*: at fit/load time the
[rounds, K, ...] level-order trees are reshaped once into contiguous
(feature, threshold, leaf) tables over a single round-major tree axis
(``NodeTables``), and ``predict_logits`` descends all N rows x T trees
in lockstep — one static-pattern column gather evaluates every
(tree, node) split comparison at once, then the level walk is pure
vector selects (``_descend``), no per-row dynamic gathers and no scan
over rounds. The host path additionally cache-blocks the tree axis
(``traverse_tables_chunked``, bit-identical — trees are independent).
The retained per-round scan (``predict_logits_scan``) is the parity
oracle; the two differ only in logit summation order (reshape-sum vs
sequential scan), so parity is bit-close, not bit-exact.

One row at a time (``row_logits``, what the controllers' ``classify``
runs per lane inside the simulation scan) takes the same tables with
compares and selects only: bins by counting edges (``bin_row``) and
each tree's leaf by a select over its leaves (``traverse_row``), with
no binary-search loop, no tree-chunk loop and no gather.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12


class NodeTables(NamedTuple):
    """Level-order trees flattened over one round-major tree axis
    (T = rounds * K, tree t = round * K + class). Internal nodes are
    heap-indexed per level (node n at depth d lives at 2^d - 1 + n), so
    every (tree, node) split comparison evaluates in one shot and
    descending a level is a short select chain per (row, tree) pair."""
    feat: jax.Array    # [T, 2^depth - 1] int32 split feature ids
    thresh: jax.Array  # [T, 2^depth - 1] int32 split bins (right if >)
    leaf: jax.Array    # [T, 2^depth] f32 leaf values (lr folded in)


def node_tables(feat: jax.Array, thresh: jax.Array,
                leaf: jax.Array) -> NodeTables:
    """[rounds, K, ...] level-order trees -> contiguous NodeTables."""
    R, K, I = feat.shape
    L = leaf.shape[-1]
    return NodeTables(
        feat=jnp.asarray(feat, jnp.int32).reshape(R * K, I),
        thresh=jnp.asarray(thresh, jnp.int32).reshape(R * K, I),
        leaf=jnp.asarray(leaf, jnp.float32).reshape(R * K, L))


def _leaf_ids(bits: jax.Array) -> jax.Array:
    """bits [N, T, I] per-node go-right decisions -> level-local leaf
    ids [N, T]. The walk is pure vector selects: at depth d the live
    node id picks this level's decision bit through a <= 2^d-way
    `jnp.where` chain — no lane-dynamic gather."""
    N, T, I = bits.shape
    depth = (I + 1).bit_length() - 1
    node = jnp.zeros((N, T), jnp.int32)
    for d in range(depth):
        base = (1 << d) - 1
        b = bits[:, :, base]
        for n in range(1, 1 << d):
            b = jnp.where(node == n, bits[:, :, base + n], b)
        node = node * 2 + b.astype(jnp.int32)
    return node


def _descend(bits: jax.Array, leaf: jax.Array) -> jax.Array:
    """bits [N, T, I], leaf [T, L] -> per-tree leaf values [N, T]: the
    `_leaf_ids` walk, then one (tree, leaf id) gather."""
    T = leaf.shape[0]
    node = _leaf_ids(bits)
    return leaf[jnp.arange(T, dtype=jnp.int32)[None, :], node]


def traverse_tables(tables: NodeTables, xb: jax.Array) -> jax.Array:
    """Descend all trees for all rows: xb [N, F] int32 bins ->
    per-tree leaf values [N, T]. One static-pattern column gather
    evaluates every (tree, node) split comparison at once
    (`jnp.take(xb, feat.reshape(-1), axis=1)` — the index vector is
    shared by all rows, so XLA lowers it as a column permutation, not a
    per-row gather), then `_descend` walks the levels with vector
    selects."""
    N = xb.shape[0]
    T, I = tables.feat.shape
    xv = jnp.take(xb, tables.feat.reshape(-1), axis=1)   # [N, T*I]
    bits = (xv > tables.thresh.reshape(-1)[None, :]).reshape(N, T, I)
    return _descend(bits, tables.leaf)


def traverse_tables_chunked(tables: NodeTables, xb: jax.Array,
                            tree_chunk: int | None = None) -> jax.Array:
    """`traverse_tables`, bit-identical, but `lax.scan`ned over chunks
    of the tree axis so the [N, tree_chunk * I] comparison plane stays
    cache-resident on CPU — the host path at large N (trees are
    independent, so chunking only reorders which tree is evaluated
    when, never any float op). `tree_chunk=None` picks the largest
    divisor of T that is <= 32."""
    N = xb.shape[0]
    T, I = tables.feat.shape
    L = tables.leaf.shape[-1]
    if tree_chunk is None:
        tree_chunk = next(tc for tc in range(min(T, 32), 0, -1)
                          if T % tc == 0)
    if tree_chunk >= T:
        return traverse_tables(tables, xb)
    tc = tree_chunk
    chunked = (tables.feat.reshape(T // tc, tc, I),
               tables.thresh.reshape(T // tc, tc, I),
               tables.leaf.reshape(T // tc, tc, L))

    def chunk(_, tabs):
        f, t, lv = tabs
        xv = jnp.take(xb, f.reshape(-1), axis=1)         # [N, tc*I]
        bits = (xv > t.reshape(-1)[None, :]).reshape(N, tc, I)
        return _, _descend(bits, lv)

    _, vals = jax.lax.scan(chunk, None, chunked)         # [C, N, tc]
    return jnp.moveaxis(vals, 0, 1).reshape(N, T)


def table_logits(base: jax.Array, tables: NodeTables, xb: jax.Array,
                 *, chunked: bool = False) -> jax.Array:
    """binned xb [N, F] -> logits [N, K] via the node tables
    (`chunked=True` takes the cache-blocked host traversal; both
    traversals are bit-identical). The per-class sum reassociates vs
    the round scan (reshape-sum), hence bit-close — not bit-exact —
    parity with `predict_logits_scan`."""
    trav = traverse_tables_chunked if chunked else traverse_tables
    vals = trav(tables, xb)                             # [N, T]
    K = base.shape[0]
    N, T = vals.shape
    return base + vals.reshape(N, T // K, K).sum(axis=1)


@dataclasses.dataclass(frozen=True)
class GBDTConfig:
    n_classes: int = 4
    n_rounds: int = 60
    depth: int = 4
    learning_rate: float = 0.25
    reg_lambda: float = 1.0
    n_bins: int = 64
    min_child_weight: float = 1e-3
    class_weighted: bool = True  # weights inversely proportional to frequency


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GBDTParams:
    """Trained ensemble. Trees are stored level-order.

    feat/thresh: [rounds, K, 2^depth - 1] split feature / bin (right if >).
    leaf:        [rounds, K, 2^depth] leaf values (learning rate folded in).
    bin_edges:   [F, n_bins - 1] quantile bin edges.
    base:        [K] initial logits (log priors).
    tables:      flattened NodeTables over the round-major tree axis —
                 derived from feat/thresh/leaf exactly once at
                 construction (fit / load / npz restore all route through
                 here), so `predict_logits` does not pay the reshape
                 per call.
    """

    feat: jax.Array
    thresh: jax.Array
    leaf: jax.Array
    bin_edges: jax.Array
    base: jax.Array
    tables: NodeTables | None = None

    def __post_init__(self):
        if self.tables is None:
            self.tables = node_tables(self.feat, self.thresh, self.leaf)

    @property
    def depth(self) -> int:
        return int(np.log2(self.leaf.shape[-1]) + 0.5)

    def tree_flatten(self):
        return ((self.feat, self.thresh, self.leaf, self.bin_edges,
                 self.base, self.tables), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def compute_bin_edges(X: np.ndarray, n_bins: int) -> np.ndarray:
    """Per-feature quantile bin edges. X [N, F] -> [F, n_bins - 1]."""
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    edges = np.quantile(X, qs, axis=0).T.astype(np.float32)  # [F, B-1]
    # strictly increasing edges keep searchsorted well-behaved on ties
    edges += np.arange(n_bins - 1, dtype=np.float32) * 1e-9
    return edges


@jax.jit
def bin_features(X: jax.Array, edges: jax.Array) -> jax.Array:
    """X [N, F], edges [F, B-1] -> int32 bins [N, F] in [0, B-1]."""
    def per_feature(col, e):
        return jnp.searchsorted(e, col, side="right").astype(jnp.int32)
    return jax.vmap(per_feature, in_axes=(1, 0), out_axes=1)(X, edges)


def _build_tree(xb, g, h, *, depth, n_bins, reg_lambda, min_child_weight):
    """Greedy level-order tree for one class.

    xb [N, F] int32 bins; g, h [N] grad/hess. Returns
    (feat [2^depth-1], thresh [2^depth-1], leaf [2^depth], leaf_id [N]).
    """
    N, F = xb.shape
    B = n_bins
    node = jnp.zeros((N,), jnp.int32)  # level-local node id
    feats_out, thresh_out = [], []
    rows = jnp.arange(N)

    for d in range(depth):
        n_nodes = 1 << d
        # (node, feature, bin) histograms via one flat segment-sum
        flat_idx = (node[:, None] * F + jnp.arange(F)[None, :]) * B + xb
        seg = n_nodes * F * B
        hist_g = jax.ops.segment_sum(
            jnp.broadcast_to(g[:, None], (N, F)).reshape(-1),
            flat_idx.reshape(-1), num_segments=seg).reshape(n_nodes, F, B)
        hist_h = jax.ops.segment_sum(
            jnp.broadcast_to(h[:, None], (N, F)).reshape(-1),
            flat_idx.reshape(-1), num_segments=seg).reshape(n_nodes, F, B)

        GL = jnp.cumsum(hist_g, axis=-1)
        HL = jnp.cumsum(hist_h, axis=-1)
        GT, HT = GL[..., -1:], HL[..., -1:]
        GR, HR = GT - GL, HT - HL
        gain = (GL**2 / (HL + reg_lambda) + GR**2 / (HR + reg_lambda)
                - GT**2 / (HT + reg_lambda))
        valid = ((HL >= min_child_weight) & (HR >= min_child_weight)
                 & (jnp.arange(B) < B - 1))
        gain = jnp.where(valid, gain, -jnp.inf)

        flat_gain = gain.reshape(n_nodes, F * B)
        best = jnp.argmax(flat_gain, axis=-1)           # [n_nodes]
        best_gain = jnp.take_along_axis(flat_gain, best[:, None], -1)[:, 0]
        bf = (best // B).astype(jnp.int32)               # split feature
        bb = (best % B).astype(jnp.int32)                # split bin
        # nodes with no valid split: degenerate split (everything left)
        no_split = ~jnp.isfinite(best_gain)
        bf = jnp.where(no_split, 0, bf)
        bb = jnp.where(no_split, B - 1, bb)              # x <= B-1 always

        feats_out.append(bf)
        thresh_out.append(bb)

        go_right = xb[rows, bf[node]] > bb[node]
        node = node * 2 + go_right.astype(jnp.int32)

    n_leaves = 1 << depth
    sum_g = jax.ops.segment_sum(g, node, num_segments=n_leaves)
    sum_h = jax.ops.segment_sum(h, node, num_segments=n_leaves)
    leaf = -sum_g / (sum_h + reg_lambda)
    return (jnp.concatenate(feats_out), jnp.concatenate(thresh_out),
            leaf, node)


@partial(jax.jit, static_argnames=("cfg",))
def _boost_round(xb, y_onehot, w, logits, cfg: GBDTConfig):
    """One boosting round: K trees (one per class). Returns new logits
    and the round's (feat [K, 2^d -1], thresh, leaf [K, 2^d])."""
    p = jax.nn.softmax(logits, axis=-1)
    G = (p - y_onehot) * w[:, None]
    H = jnp.maximum(p * (1.0 - p), 1e-6) * w[:, None]

    build = partial(_build_tree, depth=cfg.depth, n_bins=cfg.n_bins,
                    reg_lambda=cfg.reg_lambda,
                    min_child_weight=cfg.min_child_weight)
    feat, thresh, leaf, leaf_id = jax.vmap(
        lambda g, h: build(xb, g, h), in_axes=1, out_axes=0)(G, H)
    leaf = leaf * cfg.learning_rate
    delta = jax.vmap(lambda lv, li: lv[li], in_axes=0, out_axes=1)(
        leaf, leaf_id)  # [N, K]
    return logits + delta, (feat, thresh, leaf)


def fit(X: np.ndarray, y: np.ndarray, cfg: GBDTConfig = GBDTConfig(),
        *, verbose: bool = False) -> GBDTParams:
    """Train. X [N, F] float, y [N] int in [0, K)."""
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.int32)
    N, F = X.shape
    K = cfg.n_classes

    edges = compute_bin_edges(X, cfg.n_bins)
    xb = bin_features(jnp.asarray(X), jnp.asarray(edges))

    counts = np.bincount(y, minlength=K).astype(np.float64)
    priors = np.maximum(counts, 1.0) / max(N, 1)
    base = jnp.asarray(np.log(priors), jnp.float32)
    if cfg.class_weighted:
        w_cls = N / (K * np.maximum(counts, 1.0))
    else:
        w_cls = np.ones(K)
    w = jnp.asarray(w_cls, jnp.float32)[jnp.asarray(y)]
    y_onehot = jax.nn.one_hot(jnp.asarray(y), K, dtype=jnp.float32)

    logits = jnp.broadcast_to(base, (N, K))
    feats, threshs, leaves = [], [], []
    for r in range(cfg.n_rounds):
        logits, (f, t, l) = _boost_round(xb, y_onehot, w, logits, cfg)
        feats.append(f), threshs.append(t), leaves.append(l)
        if verbose and (r % 10 == 0 or r == cfg.n_rounds - 1):
            acc = float(jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(y)))
            print(f"  round {r:3d}  train_acc={acc:.4f}")

    return GBDTParams(
        feat=jnp.stack(feats), thresh=jnp.stack(threshs),
        leaf=jnp.stack(leaves), bin_edges=jnp.asarray(edges), base=base)


@jax.jit
def predict_logits(params: GBDTParams, X: jax.Array) -> jax.Array:
    """X [N, F] -> logits [N, K] via the flattened node tables (all rows
    x trees descend level-order in lockstep; no scan over rounds)."""
    xb = bin_features(X.astype(jnp.float32), params.bin_edges)
    tables = (params.tables if params.tables is not None
              else node_tables(params.feat, params.thresh, params.leaf))
    return table_logits(params.base, tables, xb, chunked=True)


def bin_row(edges: jax.Array, x: jax.Array) -> jax.Array:
    """One row x [F], edges [F, B-1] -> int32 bins [F], equal to
    `bin_features`': each bin counts the feature's edges <= x, by
    `searchsorted`'s compare-all method, which applies the same
    comparator as its binary search (NaN and +-inf included) with no
    loop and no gather."""
    def per_feature(e, v):
        return jnp.searchsorted(e, v, side="right", method="compare_all")
    return jax.vmap(per_feature)(edges, x).astype(jnp.int32)


def traverse_row(tables: NodeTables, xb: jax.Array) -> jax.Array:
    """`traverse_tables` for one row of bins xb [F] -> per-tree leaf
    values [T], equal to it: every (tree, node) split compares at once,
    `_leaf_ids` walks the levels, and each tree's leaf value is a
    select over its 2^depth leaves (exactly one matches) instead of a
    (tree, leaf id) gather. Each node's feature bin is picked by a
    product with a one-hot [F, T*I] matrix, not a column gather: bins
    are integers below 2^8 and the matrix holds 0 and 1, which bfloat16
    holds exactly, and each column sums one product, so the product is
    exact at any matmul precision."""
    T, I = tables.feat.shape
    F, L = xb.shape[0], tables.leaf.shape[-1]
    onehot = tables.feat.reshape(1, -1) == jnp.arange(F)[:, None]
    xv = xb.astype(jnp.float32) @ onehot.astype(jnp.float32)  # [T*I]
    bits = (xv > tables.thresh.reshape(-1)).reshape(1, T, I)
    node = _leaf_ids(bits)[0]                            # [T]
    vals = tables.leaf[:, 0]
    for n in range(1, L):
        vals = jnp.where(node == n, tables.leaf[:, n], vals)
    return vals


@jax.jit
def row_logits(params: GBDTParams, x: jax.Array) -> jax.Array:
    """One feature row x [F] -> logits [K] by compares, selects and a
    one-hot product (`bin_row`, `traverse_row`, then `table_logits`'
    per-class reshape-sum): the form for one row per lane inside a
    vmapped scan, where `predict_logits`' binary search, tree-chunk
    loop and leaf gather are serial, run-time-indexed loads. Bins and
    leaf values equal `predict_logits`'. Batch inference keeps
    `predict_logits`: at large N its cache-blocked gathers cost far
    less than this path's [N, F, B-1] compare plane and 2^depth selects
    per tree."""
    xb = bin_row(params.bin_edges, x.astype(jnp.float32))
    vals = traverse_row(params.tables, xb)               # [T]
    K = params.base.shape[0]
    return params.base + vals.reshape(-1, K).sum(axis=0)


@jax.jit
def predict_logits_scan(params: GBDTParams, X: jax.Array) -> jax.Array:
    """The retained per-round scan (one `apply_tree` walk per round):
    the parity oracle for the table path, and the host
    baseline bench_classification measures the table speedup against."""
    xb = bin_features(X.astype(jnp.float32), params.bin_edges)
    N = X.shape[0]
    depth = params.depth
    rows = jnp.arange(N)

    def apply_tree(feat, thresh, leaf):
        node = jnp.zeros((N,), jnp.int32)
        for d in range(depth):
            base = (1 << d) - 1
            f = feat[base + node]
            t = thresh[base + node]
            node = node * 2 + (xb[rows, f] > t).astype(jnp.int32)
        return leaf[node]  # [N]

    def per_round(logits, tree):
        feat, thresh, leaf = tree
        delta = jax.vmap(apply_tree, in_axes=0, out_axes=1)(
            feat, thresh, leaf)  # [N, K]
        return logits + delta, None

    logits0 = jnp.broadcast_to(params.base, (N, params.base.shape[0]))
    logits, _ = jax.lax.scan(
        per_round, logits0, (params.feat, params.thresh, params.leaf))
    return logits


def predict_proba(params: GBDTParams, X: jax.Array) -> jax.Array:
    return jax.nn.softmax(predict_logits(params, X), axis=-1)


def predict(params: GBDTParams, X: jax.Array) -> jax.Array:
    return jnp.argmax(predict_logits(params, X), axis=-1)


def save(params: GBDTParams, path: str) -> None:
    np.savez(path, feat=np.asarray(params.feat),
             thresh=np.asarray(params.thresh), leaf=np.asarray(params.leaf),
             bin_edges=np.asarray(params.bin_edges),
             base=np.asarray(params.base))


def load(path: str) -> GBDTParams:
    z = np.load(path)
    return GBDTParams(feat=jnp.asarray(z["feat"]),
                      thresh=jnp.asarray(z["thresh"]),
                      leaf=jnp.asarray(z["leaf"]),
                      bin_edges=jnp.asarray(z["bin_edges"]),
                      base=jnp.asarray(z["base"]))
