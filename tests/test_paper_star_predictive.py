"""The paper's setting-1 predictive pipelines at its own widths (Table 4's
star; the linear heads of Fig. 14 and the trees of Fig. 19) through
``Session``, on each side of the fused/nonfused choice, against the float64
oracle.

The star is cut to 21,000 fact rows (3,000 a year); the dimensions keep
their sf-8 sizes (80,000 / 16,000 / 2,555 rows) and every head its full
width.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fusion import LinearOperator
from repro.core.fusion.operators import reference_tree_eval, tree_from_arrays
from repro.core.query import PREDICTION, Session
from repro.core.query.workload import np_oracle, np_row_oracle
from repro.data import feature_widths, generate_star, table4_catalog

FACT_ROWS = 21_000
YEARS = 7
#: (kind, k, l or depth) of each query; l is 2**depth leaves for a tree.
HEADS = {"PL.k128.l2": ("linear", 128, 2),
         "PL.k128.l128": ("linear", 128, 128),
         "PT.k256.d7": ("tree", 256, 7),
         "PT.k16.d7": ("tree", 16, 7)}
#: A linear sum's error, over the sum of |prediction| of its rows.  The
#: program keeps float32 features, weights and predictions: each row's
#: prediction is off by a few float32 roundings (2^-24 each) and a
#: group's 3,000 rows add one more each, so its error stays well under
#: 2^-18 of the rows' sum.  Heads or features rounded to bfloat16 (2^-9)
#: err by about 2^-9 / sqrt(3000) = 4e-5 of it, over five times this.
LINEAR_TOL = 2.0 ** -18


@pytest.fixture(scope="module")
def catalog():
    return table4_catalog(sf=8, k=256, seed=15, fact_rows=FACT_ROWS)


def _features(k):
    return [tuple(f"{d}{j}" for j in range(w))
            for d, w in zip("bcd", feature_widths(k))]


def _head(kind, k, size, seed):
    rng = np.random.default_rng(seed)
    if kind == "linear":
        w = rng.normal(size=(k, size)) / math.sqrt(k)
        return LinearOperator(jnp.asarray(w, jnp.float32)), None
    p = 2 ** size - 1
    feature = rng.integers(0, k, p)
    threshold = rng.normal(size=p).astype(np.float32)
    return tree_from_arrays(feature, threshold, k), (feature, threshold)


def _query(sess, name, model):
    b, c, d = _features(model.k)
    return (sess.query("fact")
            .join("dim_b", on=("fk_b", "pk"), features=b)
            .join("dim_c", on=("fk_c", "pk"), features=c)
            .join("dim_d", on=("fk_d", "pk"), features=d)
            .predict(model)
            .group_by(("dim_d", "year", YEARS), num_groups=YEARS)
            .agg(**{name.split(".")[0]: ("sum", PREDICTION)})
            .build())


def _groups(res, agg):
    codes = np.asarray(res["groups"])
    vals = np.asarray(res[agg], np.float64)
    return {int(g): vals[i] for i, g in enumerate(codes) if g < YEARS}


def _linear_err(catalog, q, got):
    """Largest |program - oracle| over the group's sum of |prediction|."""
    want = np_oracle(dict(catalog), q)["groups"]
    rows = np_row_oracle(dict(catalog), q)
    year = np.asarray(catalog["dim_d"].key("year"))[
        np.asarray(catalog["fact"].key("fk_d"))]
    assert set(got) == set(want)
    return max(float(np.max(np.abs(got[g] - want[g]["PL"])
                            / np.abs(rows[year == g]).sum(axis=0)))
               for g in want)


@pytest.mark.parametrize("backend", ["fused", "nonfused"])
@pytest.mark.parametrize("name", sorted(HEADS))
def test_head_matches_the_oracle(catalog, name, backend):
    kind, k, size = HEADS[name]
    model, _ = _head(kind, k, size, seed=len(name) + k)
    sess = Session(catalog)
    q = _query(sess, name, model)
    plan = sess.bind(q).compile(backend=backend)
    assert plan.backend == backend
    assert dict(plan.explain().extras)["fact_backend"] == "jnp"
    res = sess.bind(q).run(backend=backend)
    assert int(res["rows"]) == FACT_ROWS
    agg = name.split(".")[0]
    got = _groups(res, agg)
    if kind == "tree":
        want = np_oracle(dict(catalog), q)["groups"]
        assert set(got) == set(want)
        for g in want:     # leaf histograms: exact counts
            np.testing.assert_array_equal(got[g], want[g][agg])
        assert sum(v.sum() for v in got.values()) == FACT_ROWS
    else:
        assert _linear_err(catalog, q, got) <= LINEAR_TOL


def test_planner_sides_at_the_papers_widths(catalog):
    """Linear heads fuse (k/l >= 1); depth-7 trees do not amortize their
    pre-fusion at these dimension sizes and run nonfused."""
    sess = Session(catalog)
    sides = {}
    for name, (kind, k, size) in HEADS.items():
        model, _ = _head(kind, k, size, seed=1)
        sides[name] = sess.bind(_query(sess, name, model)).compile().backend
    assert sides == {"PL.k128.l2": "fused", "PL.k128.l128": "fused",
                     "PT.k256.d7": "nonfused", "PT.k16.d7": "nonfused"}


def test_a_bf16_rounded_linear_head_fails_the_tolerance(catalog):
    model, _ = _head("linear", 128, 128, seed=3)
    rounded = LinearOperator(model.L.astype(jnp.bfloat16).astype(
        jnp.float32))
    sess = Session(catalog)
    q = _query(sess, "PL.k128.l128", model)
    res = sess.bind(_query(sess, "PL.k128.l128", rounded)).run()
    assert _linear_err(catalog, q, _groups(res, "PL")) > 4 * LINEAR_TOL


def test_k256_tree_leaves_match_direct_traversal(catalog):
    """Each fact row's leaf from the program's rows against a row-by-row
    walk of the tree over the joined features."""
    model, (feature, threshold) = _head("tree", 256, 7, seed=7)
    sess = Session(catalog)
    q = _query(sess, "PT.k256.d7", model)
    onehot = np.asarray(sess.bind(q).compile().predictions())
    assert (onehot.sum(axis=1) == 1).all()
    fact = catalog["fact"]
    feats = []
    for dim, cols in zip(("dim_b", "dim_c", "dim_d"), _features(256)):
        t = catalog[dim]
        m = np.asarray(t.matrix)[:, [t.col_index(c) for c in cols]]
        feats.append(m[np.asarray(fact.key("fk_" + dim[-1]))])
    x = np.concatenate(feats, axis=1)
    np.testing.assert_array_equal(onehot.argmax(axis=1),
                                  reference_tree_eval(feature, threshold, x))


def test_synthetic_star_shapes():
    s = generate_star(setting=2, sf=1, k=12, scale=0.1)
    assert s.star.feature_width == 12
    t = s.star.materialize()
    assert t.shape[1] == 12
