"""The paper's setting-1 star and its model heads, made from a seed.

Table 4 of Sun, Katsifodimos and Hai (arXiv 2306.08367), setting 1
("large input, small model"): lineorder sf x 600,000 rows, part
20,000 x floor(1 + log2 sf), supplier sf x 2,000 and date 7 x 365 rows.
``features`` N(0, 1) float32 columns are split over the three dimensions,
c = features // 3 to part and to supplier and the rest to date (paper
section 3.2); foreign keys are uniform.  The date dimension also has a
``year`` key (row // 365) for the queries to group by.

Draws the columns in the same order as the program's
``repro.data.synthetic`` (each dimension's feature columns, then the fact's
foreign keys), but is kept here so that a change to the program cannot
change the data the benchmark measures or checks.  Returns plain numpy
columns; :func:`to_program_tables` hands them to the program.

A head is made from its configuration entry and the run's seed:
:func:`head` gives a linear head's (k, l) weights or a tree's level-order
node features and thresholds as numpy arrays.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

DAYS_PER_YEAR = 365
YEARS = 7
#: (table, key column, foreign key on the fact, feature prefix), in the
#: order of the draws and of a head's features.
DIMS = (("part", "partkey", "lo_partkey", "p"),
        ("supplier", "suppkey", "lo_suppkey", "s"),
        ("date", "datekey", "lo_orderdate", "d"))
FACT = "lineorder"

#: Integer-coded columns the program keeps as exact int32 keys, per table.
KEY_COLS = {FACT: tuple(d[2] for d in DIMS), "part": ("partkey",),
            "supplier": ("suppkey",), "date": ("datekey", "year")}

Tables = Dict[str, Dict[str, np.ndarray]]


def feature_widths(k: int) -> Tuple[int, int, int]:
    """Features of a k-wide head per dimension (part, supplier, date)."""
    c = k // 3
    return c, c, k - 2 * c


def rows(sf: float) -> Tuple[int, int, int, int]:
    """(lineorder, part, supplier, date) rows at scale factor ``sf``."""
    logf = math.floor(1 + math.log2(max(sf, 1)))
    return tuple(max(int(n), 8) for n in (
        sf * 600_000, 20_000 * logf, sf * 2_000, YEARS * DAYS_PER_YEAR))


def generate(sf: float, features: int, seed: int) -> Tables:
    """Every table of the star as ``{table: {column: array}}``."""
    rng = np.random.default_rng(seed)
    n_fact, *dim_rows = rows(sf)
    out: Tables = {}
    for (name, pk, _, prefix), n, width in zip(DIMS, dim_rows,
                                               feature_widths(features)):
        cols = {f"{prefix}{j}": rng.normal(size=n).astype(np.float32)
                for j in range(width)}
        cols[pk] = np.arange(n)
        if name == "date":
            cols["year"] = np.arange(n) // DAYS_PER_YEAR
        out[name] = cols
    out[FACT] = {fk: rng.integers(0, n, n_fact)
                 for (_, _, fk, _), n in zip(DIMS, dim_rows)}
    return out


def head_features(k: int) -> List[Tuple[str, ...]]:
    """The feature columns a k-wide head reads, per dimension."""
    return [tuple(f"{prefix}{j}" for j in range(w))
            for (_, _, _, prefix), w in zip(DIMS, feature_widths(k))]


def head(spec: dict, seed: int) -> Dict[str, np.ndarray]:
    """A query's head from its ``head`` entry and the run's seed:
    ``{"L": (k, l)}`` for a linear head, ``{"feature": (p,), "threshold":
    (p,)}`` for a complete tree of ``depth`` in level order."""
    rng = np.random.default_rng([int(spec["seed"]), int(seed)])
    k = int(spec["k"])
    if spec["kind"] == "linear":
        w = rng.normal(size=(k, int(spec["l"]))) / math.sqrt(k)
        return {"L": w.astype(np.float32)}
    if spec["kind"] != "tree":
        raise ValueError(f"unknown head kind {spec['kind']!r}")
    p = 2 ** int(spec["depth"]) - 1
    return {"feature": rng.integers(0, k, p),
            "threshold": rng.normal(size=p).astype(np.float32)}


def to_program_tables(tables: Tables) -> Dict[str, object]:
    """The tables in the program's format, placed on the default device."""
    import jax
    from repro.core.laq import Table

    out = {name: Table.from_columns(name, cols, key_cols=KEY_COLS[name])
           for name, cols in tables.items()}
    jax.block_until_ready([(t.matrix, t.keys) for t in out.values()])
    return out
