"""The plain reference of the predictive queries, and its bfloat16 control.

Joins every fact row of :mod:`chipbench.stargen`'s star to its dimension
rows by a direct-address lookup, gathers the head's features, scores the
row with the head and sums per year, in numpy over blocks of rows: no
factored joins, prefusion, GEMM trees or kernels.  A linear head is
``x @ L``; a tree is walked node by node from the root (right where the
node's feature exceeds its threshold), and its leaf is counted.  It
imports nothing of the program and takes nothing the program made.

``dtype`` selects the arithmetic.  ``float64`` is the reference.  The
control (``bfloat16``) rounds every feature, weight and threshold to
bfloat16 and computes in float64: the step that would tempt a later change
(bfloat16 features or heads, or the chip's default matmul precision), which
the comparison must reject.  Run it as

    python3 -m chipbench.reference_predict --workload predict.power \\
        --seeds 1,2,3 [--sf 8]

which prints one JSON line per seed with the numbers compared.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Optional

import numpy as np

from chipbench import check, stargen
from chipbench.reference import _bf16, _lookup

#: Fact rows scored at a time.
BLOCK = 1 << 18


def _value(x: np.ndarray, dtype: str) -> np.ndarray:
    if dtype == "bfloat16":
        return _bf16(x).astype(np.float64)
    if dtype != "float64":
        raise ValueError(f"unknown reference dtype {dtype!r}")
    return np.asarray(x, np.float64)


def answer(tables, name: str, spec: dict, seed: int,
           dtype: str = "float64") -> dict:
    """A query's answer: ``{"rows", "groups": {year: {name: (vals,
    scale)}}, "exact", "aggs"}``, as :func:`chipbench.check.compare` reads
    it.  ``scale`` is the sum of |prediction| over the year's rows (the
    yardstick of a sum's rounding); a tree's leaf counts compare exactly.
    """
    h = spec["head"]
    w = stargen.head(h, seed)
    fact = tables[stargen.FACT]
    ptrs, feats = [], []
    for (table, pk, fk, _), cols in zip(stargen.DIMS,
                                        stargen.head_features(int(h["k"]))):
        dim = tables[table]
        ptrs.append(_lookup(dim[pk], fact[fk]))
        feats.append(_value(np.stack([dim[c] for c in cols], axis=1)
                            if cols else np.zeros((dim[pk].size, 0)), dtype))
    ok = np.all([p >= 0 for p in ptrs], axis=0)
    live = np.flatnonzero(ok)
    year = tables["date"]["year"][ptrs[2][live]]
    tree = h["kind"] == "tree"
    width = 2 ** int(h["depth"]) if tree else int(h["l"])
    sums = np.zeros((stargen.YEARS, width))
    scale = np.zeros((stargen.YEARS, width))
    if tree:
        feature = np.asarray(w["feature"])
        threshold = _value(w["threshold"], dtype)
    else:
        weights = _value(w["L"], dtype)
    if tree:     # each node's feature as (dimension, column)
        edges = np.cumsum([0] + [f.shape[1] for f in feats])
        node_dim = np.searchsorted(edges, feature, side="right") - 1
        node_col = feature - edges[node_dim]
    for lo in range(0, live.size, BLOCK):
        rows = live[lo:lo + BLOCK]
        yr = year[lo:lo + BLOCK]
        if tree:     # read only the feature each row's node tests
            node = np.zeros(rows.size, np.int64)
            leaf = np.zeros(rows.size, np.int64)
            for _ in range(int(h["depth"])):
                value = np.empty(rows.size)
                for d, (f, p) in enumerate(zip(feats, ptrs)):
                    at = np.flatnonzero(node_dim[node] == d)
                    value[at] = f[p[rows[at]], node_col[node[at]]]
                right = value > threshold[node]
                leaf = 2 * leaf + right
                node = 2 * node + 1 + right
            counts = np.bincount(yr * width + leaf,
                                 minlength=stargen.YEARS * width)
            sums += counts.reshape(stargen.YEARS, width)
        else:
            x = np.concatenate([f[p[rows]] for f, p in zip(feats, ptrs)],
                               axis=1)
            pred = x @ weights
            onehot = (yr[:, None] == np.arange(stargen.YEARS)[None, :])
            sums += onehot.T @ pred
            scale += onehot.T @ np.abs(pred)
    if tree:
        scale = sums
    present = np.unique(year)
    return {"rows": int(live.size),
            "groups": {int(g): {name: (sums[g], scale[g])} for g in present},
            "exact": {name} if tree else set(), "aggs": (name,)}


def agg_name(query: str) -> str:
    """The aggregate's name in a query's answer: ``score`` for a linear
    head's sums, ``leaves`` for a tree's leaf counts."""
    return "leaves" if query.startswith("PT.") else "score"


def readings(cell, seed: int, sf: Optional[float] = None) -> check.Tally:
    """The control's numbers for one seed, as a :class:`check.Tally`: the
    bfloat16 reference compared with the float64 one on every query of
    the cell's mix, over that seed's tables."""
    sf = float(cell.config["sf"] if sf is None else sf)
    tables = stored(stargen.generate(sf, cell.config["features"], seed))
    specs = cell.config["queries"]
    t = check.Tally(cell.limits)
    for n in cell.traffic["queries"]:
        a = agg_name(n)
        low = answer(tables, a, specs[n], seed, "bfloat16")
        got = {c: {a: v[a][0]} for c, v in low["groups"].items()}
        check.compare(t, n, {"group_by": ["year"]}, got, low["rows"],
                      answer(tables, a, specs[n], seed))
    return t


def stored(tables) -> Dict[str, Dict[str, np.ndarray]]:
    """The columns as the configuration stores them: float32 features and
    int32 keys, widened to float64 / int64 for the reference."""
    return {name: {c: (v.astype(np.int64) if c in stargen.KEY_COLS[name]
                       else v.astype(np.float32).astype(np.float64))
                   for c, v in cols.items()}
            for name, cols in tables.items()}


def main(argv=None) -> int:
    from chipbench.run import Cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sf", type=float, default=None)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = readings(cell, seed, args.sf)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": t.correct, "checks": t.as_dict()}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
