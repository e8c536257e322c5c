"""ML operators in linear-algebra form (paper §3.2–3.3).

* ``LinearOperator`` — a dense linear map L ∈ R^{k×l} (linear / ridge /
  logistic-regression score layers, PCA projections, ...).
* ``DecisionTreeGEMM`` — Hummingbird's GEMM representation of a decision
  tree (paper Fig. 5): binary feature-selection matrix F ∈ {0,1}^{k×p},
  threshold vector v ∈ R^p, path matrix H ∈ {−1,0,1}^{p×l}, and path-count
  vector h; prediction is ``((X·F > v)·H) == h`` yielding a one-hot leaf
  encoding per row.

  ``h`` is the per-leaf count of *positive* entries of H (the number of
  true-side nodes on the leaf's path): a row matches leaf ℓ iff every
  on-path predicate agrees, which happens exactly when the ±1-weighted sum
  reaches that count.  (The paper calls h "the column sum of H"; with the
  ±1 encoding the consistent choice is the positive part — verified against
  direct tree evaluation in tests.)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LinearOperator:
    """predictions = X @ L + bias (k → l).

    ``bias`` is optional (None ≡ zero) and exists for the rewrite engine's
    constant-input folding: an equality predicate that pins feature i to v
    removes row i from L and folds ``v · L[i, :]`` into the bias.  On the
    fused path the bias is folded into arm 0's prefused partial
    (``prefuse_dims``/``prefuse_rows``) — any arm miss invalidates the row,
    whose output is zeroed by the validity mask, so attributing the
    constant term to arm 0 is exact.
    """

    L: jnp.ndarray  # (k, l)
    bias: Optional[jnp.ndarray] = None  # (l,) or None

    @property
    def k(self) -> int:
        return int(self.L.shape[0])

    @property
    def l(self) -> int:
        return int(self.L.shape[1])

    def apply(self, x: jnp.ndarray) -> jnp.ndarray:
        out = jnp.matmul(x, self.L, precision="highest")
        if self.bias is not None:
            out = out + self.bias[None, :].astype(out.dtype)
        return out

    def apply_rows(self, x: jnp.ndarray) -> jnp.ndarray:
        """``apply`` with one add order for every row, whatever the batch.

        A dot's last bit can depend on the batch's row count and on a row's
        position in it (blocked CPU kernels fuse multiply-adds on full row
        blocks and not on the remainder), so two programs scoring one row
        in different batch shapes could disagree.  The per-row serving
        paths, which must agree bitwise across batch shapes and meshes,
        sum the k products in feature order instead.
        """
        out = jnp.sum(x[:, :, None] * self.L[None, :, :].astype(x.dtype),
                      axis=1)
        if self.bias is not None:
            out = out + self.bias[None, :].astype(out.dtype)
        return out

    def compose(self, other: "LinearOperator") -> "LinearOperator":
        """Associativity: (X L₁) L₂ = X (L₁ L₂) — pre-fold chained layers."""
        bias = None
        if self.bias is not None:
            bias = jnp.matmul(self.bias, other.L, precision="highest")
        if other.bias is not None:
            bias = other.bias if bias is None else bias + other.bias
        return LinearOperator(
            jnp.matmul(self.L, other.L, precision="highest"), bias)


@dataclasses.dataclass(frozen=True)
class DecisionTreeGEMM:
    """Hummingbird GEMM decision tree: ((X F > v) H) == h."""

    F: jnp.ndarray  # (k, p) {0,1} feature selection, one 1 per column
    v: jnp.ndarray  # (p,) node thresholds
    H: jnp.ndarray  # (p, l) {−1,0,1} leaf paths
    h: jnp.ndarray  # (l,) positive-entry count per column of H

    @property
    def k(self) -> int:
        return int(self.F.shape[0])

    @property
    def p(self) -> int:
        return int(self.F.shape[1])

    @property
    def l(self) -> int:
        return int(self.H.shape[1])

    def predicates(self, x: jnp.ndarray) -> jnp.ndarray:
        """Step 1–2: (X F > v) ∈ {0,1}^{i×p}."""
        feats = jnp.matmul(x, self.F, precision="highest")
        return (feats > self.v[None, :]).astype(x.dtype)

    def apply(self, x: jnp.ndarray) -> jnp.ndarray:
        """One-hot leaf encoding (i × l) — steps 1–4 of Fig. 5."""
        b = self.predicates(x)
        score = b @ self.H.astype(x.dtype)
        return (score == self.h[None, :].astype(x.dtype)).astype(x.dtype)

    # Leaf encodings are exact (0/1 compares of small integer sums), so the
    # per-row serving paths need no add-order guarantee beyond ``apply``.
    apply_rows = apply

    def predict_leaf(self, x: jnp.ndarray) -> jnp.ndarray:
        """Leaf index per row (argmax over the one-hot encoding)."""
        return jnp.argmax(self.apply(x), axis=1)


# --------------------------------------------------------------------------
# Tree construction helpers
# --------------------------------------------------------------------------
def tree_from_arrays(feature: np.ndarray, threshold: np.ndarray, k: int
                     ) -> DecisionTreeGEMM:
    """Build the GEMM form of a *complete* binary tree.

    ``feature[n]``/``threshold[n]`` describe internal node n in level order
    (n ∈ [0, 2^d − 1)); leaves are the 2^d paths.
    """
    p = int(feature.shape[0])
    depth = int(np.log2(p + 1))
    l = p + 1
    F = np.zeros((k, p), np.float32)
    F[feature, np.arange(p)] = 1.0
    H = np.zeros((p, l), np.float32)
    for leaf in range(l):
        node = 0
        for level in range(depth):
            # Bit `depth-1-level` of the leaf id picks the branch at `node`.
            go_right = (leaf >> (depth - 1 - level)) & 1
            H[node, leaf] = 1.0 if go_right else -1.0
            node = 2 * node + 1 + go_right
    h = np.maximum(H, 0.0).sum(axis=0)
    return DecisionTreeGEMM(jnp.asarray(F), jnp.asarray(threshold, np.float32),
                            jnp.asarray(H), jnp.asarray(h, np.float32))


def random_tree(rng: np.random.Generator, k: int, depth: int,
                scale: float = 1.0) -> DecisionTreeGEMM:
    """A random complete tree over k features (benchmarks / tests)."""
    p = 2**depth - 1
    feature = rng.integers(0, k, size=p)
    threshold = rng.normal(0.0, scale, size=p).astype(np.float32)
    return tree_from_arrays(feature, threshold, k)


def reference_tree_eval(feature: np.ndarray, threshold: np.ndarray,
                        x: np.ndarray) -> np.ndarray:
    """Direct (non-LA) tree traversal oracle: leaf index per row."""
    p = feature.shape[0]
    depth = int(np.log2(p + 1))
    out = np.zeros((x.shape[0],), np.int64)
    for r in range(x.shape[0]):
        node = 0
        leaf = 0
        for _ in range(depth):
            right = x[r, feature[node]] > threshold[node]
            leaf = (leaf << 1) | int(right)
            node = 2 * node + 1 + int(right)
        out[r] = leaf
    return out
