"""Synthetic star schema for the operator-fusion experiments (paper Table 4).

Two cardinality settings:
  setting 1: lineorder sf·600,000; part 20,000·⌊1+log2 sf⌋; supplier sf·2,000;
             date 7·365   — "large input, small model"
  setting 2: lineorder sf·3,000;   part  2,000·⌊1+log2 sf⌋; supplier sf·2,000;
             date 7·365   — "small input, large model"
Feature columns are split evenly across the three dimension tables
(paper §3.2: c = k/3) and filled with N(0,1) floats.  Foreign keys are
uniform.  :func:`table4_catalog` gives setting 1 as a catalog, with a year
key on the date dimension for grouped predictive queries.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

from repro.core.laq import Catalog, DimSpec, StarJoin, Table, star_join

DAYS_PER_YEAR = 365


@dataclasses.dataclass
class SyntheticStar:
    star: StarJoin
    k: int               # total feature columns
    n_fact: int
    dim_rows: Tuple[int, int, int]


def cardinalities(setting: int, sf: float):
    logf = math.floor(1 + math.log2(max(sf, 1)))
    if setting == 1:
        return (int(sf * 600_000), int(20_000 * logf), int(sf * 2_000),
                7 * 365)
    return (int(sf * 3_000), int(2_000 * logf), int(sf * 2_000), 7 * 365)


def feature_widths(k: int) -> Tuple[int, int, int]:
    """Features per dimension: c = k/3 each, the remainder in the last."""
    c = k // 3
    return c, c, k - 2 * c


def _tables(rng: np.random.Generator, rows: Tuple[int, ...], k: int,
            year: bool = False):
    """The fact and its three dimensions, drawn in a fixed order: each
    dimension's feature columns, then the fact's foreign keys."""
    n_fact, *dim_rows = rows
    specs = []
    for name, n_rows, width in zip("bcd", dim_rows, feature_widths(k)):
        cols = {f"{name}{j}": rng.normal(size=n_rows).astype(np.float32)
                for j in range(width)}
        cols["pk"] = np.arange(n_rows)
        keys = ("pk",)
        if year and name == "d":       # the date dimension, one row a day
            cols["year"] = np.arange(n_rows) // DAYS_PER_YEAR
            keys = ("pk", "year")
        dim = Table.from_columns(f"dim_{name}", cols, key_cols=keys)
        specs.append((dim, n_rows, tuple(f"{name}{j}" for j in range(width))))

    fact_cols = {
        f"fk_{name}": rng.integers(0, n_rows, n_fact)
        for (dim, n_rows, _), name in zip(specs, "bcd")
    }
    fact = Table.from_columns("fact", fact_cols,
                              key_cols=tuple(fact_cols.keys()))
    return fact, specs


def generate(setting: int, sf: float, k: int, seed: int = 0,
             scale: float = 1.0) -> SyntheticStar:
    """Build the star join for cardinality ``setting`` with k features."""
    rows = tuple(max(int(n * scale), 8) for n in cardinalities(setting, sf))
    fact, specs = _tables(np.random.default_rng(seed), rows, k)
    dim_specs = [DimSpec(dim, f"fk_{name}", "pk", feats)
                 for (dim, _, feats), name in zip(specs, "bcd")]
    return SyntheticStar(star=star_join(fact, dim_specs), k=k,
                         n_fact=rows[0],
                         dim_rows=(specs[0][1], specs[1][1], specs[2][1]))


def table4_catalog(sf: float = 8, k: int = 256, seed: int = 0,
                   fact_rows: Optional[int] = None) -> Catalog:
    """Setting 1 of Table 4 as a :class:`Catalog`, with a year to group by.

    The tables of :func:`generate` (``fact``; ``dim_b``, ``dim_c``,
    ``dim_d`` for part, supplier and date), drawn in the same order, so one
    seed gives the same columns; the date dimension also has a ``year`` key
    column (row // 365: 7 years).  ``fact_rows`` cuts the fact alone, the
    dimensions keep their size.
    """
    n_fact, *dims = cardinalities(1, sf)
    rows = (n_fact if fact_rows is None else int(fact_rows), *dims)
    fact, specs = _tables(np.random.default_rng(seed), rows, k, year=True)
    return Catalog({fact.name: fact, **{d.name: d for d, _, _ in specs}})
