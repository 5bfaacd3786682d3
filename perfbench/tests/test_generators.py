"""The generators keep to dbgen's and dsdgen's rules (the configurations'
files state them): row counts and key domains, derived columns, NULL
shares, and that any subset of columns comes out the same as in the whole
table (set-up makes only the columns a query reads; the reference makes
them again)."""
import json
import os

import numpy as np

import datagen
import run


def _config(name):
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_lineitem_follows_dbgen():
    config = _config("tpch_sf10")
    tables = datagen.scaled_tables(config, 300_000)
    gen = datagen.load_module("generators", config["generator"])
    whole = gen.generate("lineitem", tables, 2**31 + 7, 3, 300_000)
    made = set(whole.column_names)
    assert made == set(tables["lineitem"]["columns"]) and len(made) == 15
    part = gen.generate("lineitem", tables, 2**31 + 7, 3, 300_000,
                        ["l_shipdate", "l_returnflag", "l_extendedprice"])
    assert part.equals(whole.select(part.column_names))
    df = whole.to_pandas(date_as_object=False)
    current = np.datetime64("1995-06-17")
    assert ((df.l_returnflag == "N") == (df.l_receiptdate > current)).all()
    assert ((df.l_linestatus == "O") == (df.l_shipdate > current)).all()
    groups = df.groupby(["l_returnflag", "l_linestatus"]).size()
    assert sorted(groups.index) == [("A", "F"), ("N", "F"), ("N", "O"),
                                    ("R", "F")]
    assert groups["N", "F"] < 0.02 * len(df) < 0.2 * len(df) < groups["A", "F"]
    days = (df.l_receiptdate - df.l_shipdate).dt.days
    assert days.min() == 1 and days.max() == 30
    p = df.l_partkey
    retail = (90000 + (p // 10) % 20001 + 100 * (p % 1000)) / 100.0
    assert np.allclose(df.l_extendedprice, df.l_quantity * retail,
                       rtol=0, atol=1e-6)
    lines = df.groupby("l_orderkey").size()
    assert lines.iloc[:-1].between(1, 7).all() and 3.8 < lines.mean() < 4.2
    assert df.l_shipdate.max() <= np.datetime64("1998-12-01")


def test_star_schema_follows_dsdgen():
    config = _config("tpcds_sf1_star")
    tables = datagen.scaled_tables(config, 400_000)
    gen = datagen.load_module("generators", config["generator"])
    dates = gen.generate("date_dim", tables, 5, 0, 73049).to_pandas(
        date_as_object=False)
    assert dates.d_date_sk.iloc[0] == 2415022
    assert str(dates.d_date.iloc[0].date()) == "1900-01-02"
    assert str(dates.d_date.iloc[-1].date()) == "2100-01-01"
    item = gen.generate("item", tables, 5, 0, 18000).to_pandas()
    assert item.i_item_sk.min() == 1 and item.i_item_sk.max() == 18000
    assert item.i_manufact_id.between(1, 1000).all()
    assert item.i_brand_id.between(1001001, 10016006).all()
    assert item.groupby("i_brand_id").i_brand.nunique().max() == 1
    sales = gen.generate("store_sales", tables, 5, 1, 400_000)
    assert gen.generate("store_sales", tables, 5, 1, 400_000,
                        ["ss_item_sk"]).equals(sales.select(["ss_item_sk"]))
    ss = sales.to_pandas()
    assert ss.ss_item_sk.notna().all()
    for c in ("ss_sold_date_sk", "ss_ext_sales_price"):
        assert 0.04 < ss[c].isna().mean() < 0.05
    sold = ss.merge(dates, left_on="ss_sold_date_sk", right_on="d_date_sk")
    assert sold.d_date.min() >= np.datetime64("1998-01-02")
    assert sold.d_date.max() <= np.datetime64("2003-01-02")
    november = (sold.d_moy == 11).mean()
    assert 0.14 < november < 0.17, november
