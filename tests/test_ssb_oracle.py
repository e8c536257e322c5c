"""SSB queries through the LAQ engine vs brute-force numpy oracles."""
import numpy as np
import pytest

from repro.core.laq import PAD_GROUP, decode_composite
from repro.data import QUERIES, generate_ssb
from repro.data.ssb import N_BRANDS


@pytest.fixture(scope="module")
def data():
    return generate_ssb(sf=1, scale=0.001, seed=3)


def _np_cols(table, *cols):
    n = int(table.nvalid)
    out = []
    for c in cols:
        src = table.keys.get(c)
        out.append(np.asarray(src)[:n] if src is not None
                   else np.asarray(table.matrix)[:n, table.col_index(c)])
    return out


@pytest.mark.slow
def test_q11_matches_bruteforce(data):
    lo, date = data.lineorder, data.date
    od, disc, qty, price = _np_cols(lo, "lo_orderdate", "lo_discount",
                                    "lo_quantity", "lo_extendedprice")
    dk, year = _np_cols(date, "datekey", "d_year")
    y = {int(k): int(v) for k, v in zip(dk, year)}
    mask = (np.vectorize(lambda k: y.get(int(k), 0))(od) == 1993)
    mask &= (disc >= 1) & (disc <= 3) & (qty < 25)
    want_rows = int(mask.sum())
    want_rev = float((price[mask] * disc[mask]).sum())
    got = QUERIES["Q1.1"](data)
    assert int(got["rows"]) == want_rows
    assert float(got["revenue"]) == pytest.approx(want_rev, rel=1e-5)


@pytest.mark.slow
def test_q21_groups_match_bruteforce(data):
    lo, date, part, supp = (data.lineorder, data.date, data.part,
                            data.supplier)
    od, pk_fk, sk_fk, rev = _np_cols(lo, "lo_orderdate", "lo_partkey",
                                     "lo_suppkey", "lo_revenue")
    dk, year = _np_cols(date, "datekey", "d_year")
    ppk, cat, brand = _np_cols(part, "partkey", "p_category", "p_brand1")
    spk, sreg = _np_cols(supp, "suppkey", "s_region")
    ymap = {int(k): int(v) for k, v in zip(dk, year)}
    pmap = {int(k): (int(c), int(b)) for k, c, b in zip(ppk, cat, brand)}
    smap = {int(k): int(r) for k, r in zip(spk, sreg)}
    want = {}
    for i in range(len(od)):
        p = pmap.get(int(pk_fk[i]))
        s = smap.get(int(sk_fk[i]))
        yv = ymap.get(int(od[i]))
        if p is None or s is None or yv is None:
            continue
        if p[0] == 6 and s == 1:  # category == 6, region == 1
            key = (yv, p[1])
            want[key] = want.get(key, 0.0) + float(rev[i])
    got = QUERIES["Q2.1"](data)
    groups = np.asarray(got["groups"])
    revs = np.asarray(got["revenue"])
    live = groups != PAD_GROUP
    yr, br = decode_composite(groups[live], [8, N_BRANDS])
    got_map = {(int(y) + 1992, int(b)): float(r)
               for y, b, r in zip(np.asarray(yr), np.asarray(br), revs[live])
               if float(r) != 0.0}
    for key, val in want.items():
        assert got_map.get(key, 0.0) == pytest.approx(val, rel=1e-4), key
    for key, val in got_map.items():
        assert key in want or val == pytest.approx(0.0, abs=1e-3)


@pytest.mark.slow
def test_q41_profit_total_matches_bruteforce(data):
    lo = data.lineorder
    ck, sk, pk, od, rev, cost = _np_cols(
        lo, "lo_custkey", "lo_suppkey", "lo_partkey", "lo_orderdate",
        "lo_revenue", "lo_supplycost")
    cpk, creg = _np_cols(data.customer, "custkey", "c_region")
    spk, sreg = _np_cols(data.supplier, "suppkey", "s_region")
    ppk, mfgr = _np_cols(data.part, "partkey", "p_mfgr")
    dk = _np_cols(data.date, "datekey")[0]
    cmap = {int(k): int(v) for k, v in zip(cpk, creg)}
    smap = {int(k): int(v) for k, v in zip(spk, sreg)}
    pmap = {int(k): int(v) for k, v in zip(ppk, mfgr)}
    dset = set(int(k) for k in dk)
    total = 0.0
    nrows = 0
    for i in range(len(ck)):
        if (cmap.get(int(ck[i])) == 1 and smap.get(int(sk[i])) == 1
                and pmap.get(int(pk[i])) in (0, 1) and int(od[i]) in dset):
            total += float(rev[i]) - float(cost[i])
            nrows += 1
    got = QUERIES["Q4.1"](data)
    assert int(got["rows"]) == nrows
    assert float(np.asarray(got["profit"]).sum()) == pytest.approx(
        total, rel=1e-4)


def test_ssb_generator_and_query_sanity():
    data = generate_ssb(sf=1, scale=0.002, seed=0)
    res = QUERIES["Q1.1"](data)
    assert float(res["rows"]) > 0
    assert np.isfinite(float(res["revenue"]))
    res4 = QUERIES["Q4.2"](data)
    n_groups_hit = int(np.sum(np.asarray(res4["profit"]) != 0))
    assert n_groups_hit > 0
