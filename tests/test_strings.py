"""String expression tests (ref string_test.py, regexp_test.py).

Strings are host-Arrow in both engines, so these validate against explicit
Python-computed expected values rather than differentially.
"""
import pandas as pd
import pytest

from harness import assert_tpu_and_cpu_equal, tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.exprs import RegexUnsupported, transpile_java_regex


DATA = ["hello World", "", None, "Spark RAPIDS tpu", "aaa bbb  ccc",
        "héllo 中文", "x,y,z", "  padded  "]


def _df(s):
    return s.create_dataframe(pd.DataFrame({"s": DATA}))


def _run(col):
    s = tpu_session()
    out = _df(s).select(col.alias("r")).to_pandas()["r"].tolist()
    # normalize pandas NaN->None and nullable floats back to ints
    norm = []
    for v in out:
        if v is None or (isinstance(v, float) and pd.isna(v)):
            norm.append(None)
        elif isinstance(v, float) and v.is_integer():
            norm.append(int(v))
        else:
            norm.append(v)
    return norm


def _pyexpect(fn):
    return [None if v is None else fn(v) for v in DATA]


def test_length_upper_lower():
    assert _run(F.length(F.col("s"))) == _pyexpect(len)
    assert _run(F.upper(F.col("s"))) == _pyexpect(str.upper)
    assert _run(F.lower(F.col("s"))) == _pyexpect(str.lower)


def test_substring():
    assert _run(F.substring(F.col("s"), 1, 3)) == _pyexpect(lambda v: v[:3])
    assert _run(F.substring(F.col("s"), 2, 2)) == _pyexpect(lambda v: v[1:3])
    assert _run(F.substring(F.col("s"), -3)) == _pyexpect(lambda v: v[-3:])


def test_concat_null_propagates():
    out = _run(F.concat(F.col("s"), F.lit("!")))
    assert out == [None if v is None else v + "!" for v in DATA]


def test_predicates():
    assert _run(F.contains(F.col("s"), "o")) == _pyexpect(lambda v: "o" in v)
    assert _run(F.startswith(F.col("s"), "h")) == \
        _pyexpect(lambda v: v.startswith("h"))
    assert _run(F.endswith(F.col("s"), "c")) == \
        _pyexpect(lambda v: v.endswith("c"))


def test_like():
    out = _run(F.like(F.col("s"), "%o%d"))
    import re
    assert out == _pyexpect(lambda v: re.fullmatch(".*o.*d", v) is not None)


def test_trim_pad_reverse_repeat():
    assert _run(F.trim(F.col("s"))) == _pyexpect(str.strip)
    assert _run(F.ltrim(F.col("s"))) == _pyexpect(str.lstrip)
    assert _run(F.rpad(F.col("s"), 4, "*")) == \
        _pyexpect(lambda v: v.ljust(4, "*")[:4])
    assert _run(F.reverse(F.col("s"))) == _pyexpect(lambda v: v[::-1])
    assert _run(F.repeat(F.col("s"), 2)) == _pyexpect(lambda v: v * 2)


def test_regexp_replace_extract():
    assert _run(F.regexp_replace(F.col("s"), "[aeiou]", "#")) == \
        _pyexpect(lambda v: __import__("re").sub("[aeiou]", "#", v))
    out = _run(F.regexp_extract(F.col("s"), "(\\w+)", 1))
    import re
    rx = re.compile("([a-zA-Z0-9_]+)")
    assert out == _pyexpect(
        lambda v: (rx.search(v).group(1) if rx.search(v) else ""))


def test_substring_index_and_locate():
    assert _run(F.substring_index(F.col("s"), " ", 1)) == \
        _pyexpect(lambda v: v.split(" ")[0] if " " in v else v)
    assert _run(F.locate("o", F.col("s"))) == \
        _pyexpect(lambda v: v.find("o") + 1)


def test_filter_on_string_predicate_mixed_plan():
    """Plain-column string predicates now stay on the device filter
    (dictionary evaluation); predicates over COMPUTED strings still fall
    back to the CPU filter (per-exec fallback like the reference)."""
    s = tpu_session()
    df = s.create_dataframe(pd.DataFrame(
        {"s": ["aa", "ab", "ba", None], "v": [1, 2, 3, 4]}))
    out = (df.filter(F.startswith(F.col("s"), "a"))
           .select((F.col("v") * 10).alias("v10")))
    tree = out._physical().tree_string()
    assert "CpuFilter" not in tree and "* Project" in tree
    assert sorted(out.to_pandas()["v10"]) == [10, 20]

    out2 = (df.filter(F.startswith(F.upper(F.col("s")), "A"))
            .select((F.col("v") * 10).alias("v10")))
    tree2 = out2._physical().tree_string()
    assert "CpuFilter" in tree2, tree2
    assert sorted(out2.to_pandas()["v10"]) == [10, 20]


class TestRegexTranspiler:
    def test_ascii_classes(self):
        assert transpile_java_regex("\\d+") == "[0-9]+"
        assert transpile_java_regex("\\w") == "[a-zA-Z0-9_]"
        assert transpile_java_regex("[\\d]") == "[0-9]"

    def test_passthrough(self):
        assert transpile_java_regex("a(b|c)*d") == "a(b|c)*d"
        # `$` is NOT passthrough: Java's matches before a final
        # line terminator (r3 fix)
        import re as _re
        p = transpile_java_regex("^x{2,3}$")
        assert _re.search(p, "xx\n") and _re.search(p, "xxx")
        assert not _re.search(p, "xx\ny") and not _re.search(p, "x")

    def test_named_group(self):
        assert transpile_java_regex("(?<nm>a)") == "(?P<nm>a)"

    def test_java_z(self):
        assert transpile_java_regex("a\\z") == "a\\Z"

    @pytest.mark.parametrize("bad", ["\\p{L}", "[a[^b]]",
                                     "[a&&b]", "\\G", "(?"  "u)x"])
    def test_rejected(self, bad):
        with pytest.raises(RegexUnsupported):
            transpile_java_regex(bad)

    def test_unbalanced(self):
        with pytest.raises(RegexUnsupported):
            transpile_java_regex("(a")
        with pytest.raises(RegexUnsupported):
            transpile_java_regex("a)")


# ---------------------------------------------------------------------------
# dictionary-evaluated string predicates (VERDICT r1 #5): predicates run
# once over the sorted dictionary, broadcast through codes on device
# ---------------------------------------------------------------------------

def _str_table(n=2000, card=30, seed=3):
    import numpy as np
    import pyarrow as pa
    rng = np.random.RandomState(seed)
    words = [f"{p}_{i:03d}" for i, p in zip(
        range(card), ["apple", "apricot", "banana", "cherry", "date"] * card)]
    vals = [None if rng.rand() < 0.05 else words[rng.randint(card)]
            for _ in range(n)]
    return pa.table({"s": pa.array(vals),
                     "v": pa.array(rng.randint(0, 100, n).astype("int64"))})


def test_dict_filter_contains_differential():
    t = _str_table()

    def q(s):
        return (s.create_dataframe(t)
                .filter(F.col("s").contains("pri") & (F.col("v") > F.lit(10)))
                .agg(F.count_star().with_name("c"),
                     F.sum(F.col("v")).with_name("sv")))
    assert_tpu_and_cpu_equal(q)


def test_dict_filter_startswith_range_form():
    t = _str_table()

    def q(s):
        return (s.create_dataframe(t)
                .filter(F.col("s").startswith("ap"))
                .agg(F.count_star().with_name("c")))
    assert_tpu_and_cpu_equal(q)


def test_dict_filter_like_and_or():
    t = _str_table()

    def q(s):
        return (s.create_dataframe(t)
                .filter(F.col("s").like("%an%a%")
                        | (F.col("s").startswith("date")
                           & (F.col("v") < F.lit(50))))
                .agg(F.count_star().with_name("c"),
                     F.min(F.col("v")).with_name("mn")))
    assert_tpu_and_cpu_equal(q)


def test_dict_filter_stays_on_device_plan():
    t = _str_table()
    s = tpu_session()
    df = (s.create_dataframe(t)
          .filter(F.col("s").contains("err"))
          .agg(F.count_star().with_name("c")))
    tree = df._physical().tree_string()
    assert "CpuFilter" not in tree, tree
    assert "Filter" in tree


def test_dict_filter_string_output_columns_survive():
    """Filtered batches keep the string column intact (codes compacted on
    device, decode at the sink)."""
    t = _str_table(n=500)

    def q(s):
        return (s.create_dataframe(t)
                .filter(F.col("s").endswith("_001")))
    got = assert_tpu_and_cpu_equal(q)
    assert all(x.endswith("_001") for x in got["s"])


class TestRegexTranspilerR2:
    """Round-2 depth: \\Z, \\R, POSIX classes, nested unions, ASCII
    boundaries (ref RegexParser.scala coverage)."""

    def test_end_anchor_Z(self):
        import re
        p = transpile_java_regex("abc\\Z")
        assert re.search(p, "abc\n")      # before final terminator
        assert re.search(p, "abc")
        assert not re.search(p, "abc\n\n")

    def test_any_linebreak_R(self):
        import re
        p = transpile_java_regex("a\\Rb")
        assert re.search(p, "a\r\nb") and re.search(p, "a\nb")
        assert not re.search(p, "a b")

    def test_posix_classes(self):
        import re
        p = transpile_java_regex("\\p{Alpha}+\\p{Digit}")
        assert re.fullmatch(p, "abc7")
        assert not re.fullmatch(p, "ab7c")
        pn = transpile_java_regex("\\P{Digit}")
        assert re.fullmatch(pn, "x") and not re.fullmatch(pn, "5")
        pin = transpile_java_regex("[\\p{Upper}0-3]+")
        assert re.fullmatch(pin, "AB2")

    def test_unicode_category_rejected(self):
        with pytest.raises(RegexUnsupported):
            transpile_java_regex("\\p{L}+")

    def test_nested_class_union(self):
        import re
        p = transpile_java_regex("[a[bc]]+")
        assert re.fullmatch(p, "cab")
        assert not re.fullmatch(p, "d")
        with pytest.raises(RegexUnsupported):
            transpile_java_regex("[a[^b]]")
        with pytest.raises(RegexUnsupported):
            transpile_java_regex("[a&&[b]]")

    def test_ascii_word_boundary(self):
        import re
        p = transpile_java_regex("\\bword\\b")
        assert re.search(p, "a word here")
        # Java's ASCII \b: a unicode letter is NOT a word char
        assert re.search(p, "éwordé")

    def test_rlike_uses_extended_transpiler(self):
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame(
            {"s": ["abc1", "xyz", "ABC2", None]}))
        out = df.filter(F.rlike(F.col("s"), "\\p{Alpha}+\\p{Digit}")) \
            .to_pandas()
        assert sorted(out["s"]) == ["ABC2", "abc1"]


class TestRegexTargets:
    """The transpiler emits per-target syntax: RLike/RegExpReplace/
    StringSplit execute on pyarrow's RE2 engine (no lookaround, ASCII
    \\b already), RegExpExtract on Python re. These route boundary and
    anchor patterns END TO END through each engine (advisor r2 high)."""

    def test_rlike_word_boundary_end_to_end(self):
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame(
            {"s": ["a word here", "sword", "word", None]}))
        out = df.filter(F.rlike(F.col("s"), "\\bword\\b")).to_pandas()
        assert sorted(out["s"]) == ["a word here", "word"]

    def test_regexp_replace_word_boundary_end_to_end(self):
        assert _run(F.regexp_replace(F.col("s"), "\\bWorld\\b", "X")) == \
            _pyexpect(lambda v: v.replace("World", "X"))

    def test_rlike_end_anchor_Z_java_semantics(self):
        # Java \Z matches before one FINAL line terminator; in boolean
        # find mode the RE2 rewrite may consume it (same verdict)
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame(
            {"s": ["x", "x\n", "x\r\n", "x\n\n", "x\ny"]}))
        out = df.filter(F.rlike(F.col("s"), "x\\Z")).to_pandas()
        assert sorted(out["s"]) == ["x", "x\n", "x\r\n"]

    def test_rlike_dollar_java_semantics(self):
        # Java non-multiline $ == \Z (r3 review finding: RE2 $ is
        # end-of-text only, silently dropping the "x\n" row before)
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame(
            {"s": ["x", "x\n", "x\r", "x\n\n", "xy"]}))
        out = df.filter(F.rlike(F.col("s"), "x$")).to_pandas()
        assert sorted(out["s"]) == ["x", "x\n", "x\r"]

    def test_regexp_replace_dollar_keeps_terminator(self):
        # replace mode must NOT consume the final \n -> falls back to
        # the Python-re row loop where the lookahead rewrite applies
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame({"s": ["ax\n", "ax", "ay"]}))
        out = df.select(
            F.regexp_replace(F.col("s"), "x$", "Z").alias("r")
        ).to_pandas()["r"].tolist()
        assert out == ["aZ\n", "aZ", "ay"]

    def test_dot_excludes_java_line_terminators(self):
        # Java `.` excludes \r \x85    , not just \n
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame(
            {"s": ["a\rb", "a\nb", "a\x85b", "axb"]}))
        out = df.filter(F.rlike(F.col("s"), "a.b")).to_pandas()
        assert sorted(out["s"]) == ["axb"]
        # (?s) global prefix restores match-anything dot
        out = df.filter(F.rlike(F.col("s"), "(?s)a.b")).to_pandas()
        assert len(out) == 4

    def test_multiline_flag_rejected(self):
        with pytest.raises(RegexUnsupported):
            transpile_java_regex("(?m)^x$")
        with pytest.raises(RegexUnsupported):
            transpile_java_regex("a(?m:x$)b", target="re2")

    def test_rlike_lookaround_falls_back_to_python_engine(self):
        # RE2 can't run lookarounds; RLike transparently row-loops
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame(
            {"s": ["price: 10", "price: 9", None]}))
        out = df.filter(F.rlike(F.col("s"), "price: (?=1)\\d+")) \
            .to_pandas()
        assert out["s"].tolist() == ["price: 10"]

    def test_rlike_java_z_anchor(self):
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame({"s": ["x", "x\n", "ax"]}))
        out = df.filter(F.rlike(F.col("s"), "x\\z")).to_pandas()
        assert sorted(out["s"]) == ["ax", "x"]

    def test_regexp_extract_keeps_python_target(self):
        # extract runs on Python re, where \Z/\b rewrites still apply
        out = _run(F.regexp_extract(F.col("s"), "(\\w+)\\Z", 1))
        import re
        exp = []
        for v in DATA:
            if v is None:
                exp.append(None)
            else:
                m = re.search(r"(?a:(\w+))(?=\n?\Z)", v)
                exp.append("" if m is None else m.group(1))
        assert out == exp

    def test_re2_rejections_are_plan_time(self):
        for pat in ["(?=x)y", "(?<=x)y", "(?>xy)", "(x)\\1"]:
            with pytest.raises(RegexUnsupported):
                transpile_java_regex(pat, target="re2")
        # ...but python target keeps lookarounds
        assert transpile_java_regex("(?=x)y") == "(?=x)y"

    def test_linebreak_R_both_targets(self):
        s = tpu_session()
        df = s.create_dataframe(pd.DataFrame(
            {"s": ["a\nb", "a\r\nb", "a b", "ab"]}))
        out = df.filter(F.rlike(F.col("s"), "a\\Rb")).to_pandas()
        assert len(out) == 3


def test_split_limit_semantics_both_engines():
    """Spark limit: >0 = at most limit elements, <=0 = unlimited.
    Python re.split inverts the special maxsplit values (r3 review
    finding) — pin both the RE2 path and the lookahead-forced
    Python-re fallback."""
    s = tpu_session()
    df = s.create_dataframe(pd.DataFrame({"s": ["a:1b:2c:3d"]}))

    def run(pat, lim):
        return _df_split(df, pat, lim)

    def _df_split(df, pat, lim):
        out = df.select(
            F.split(F.col("s"), pat, lim).alias("r")).to_pandas()
        return list(out["r"][0])

    for pat in [":", ":(?=\\d)"]:        # RE2 path / python fallback
        assert _df_split(df, pat, -1) == ["a", "1b", "2c", "3d"]
        assert _df_split(df, pat, 0) == ["a", "1b", "2c", "3d"]
        assert _df_split(df, pat, 1) == ["a:1b:2c:3d"]
        assert _df_split(df, pat, 2) == ["a", "1b:2c:3d"]
        assert _df_split(df, pat, 3) == ["a", "1b", "2c:3d"]


def test_split_limit_zero_drops_trailing_empties():
    """Java Pattern.split limit=0: unlimited splits THEN trailing empty
    strings removed; limit=-1 keeps them (r3 review finding)."""
    s = tpu_session()
    df = s.create_dataframe(pd.DataFrame({"s": ["a:b::", "::", "a"]}))

    def run(pat, lim):
        out = df.select(
            F.split(F.col("s"), pat, lim).alias("r")).to_pandas()
        return [list(x) for x in out["r"]]

    for pat in [":", ":(?=.?)"]:          # RE2 path / python fallback
        assert run(pat, 0) == [["a", "b"], [], ["a"]]
        assert run(pat, -1) == [["a", "b", "", ""], ["", "", ""], ["a"]]


class TestDictTransforms:
    """Value-wise string transforms over dictionary-coded columns
    evaluate ONCE per distinct entry and re-encode (VERDICT r2 #4):
    row data never takes the per-row host detour."""

    def _dict_df(self, n=5000):
        s = tpu_session()
        rng = __import__("numpy").random.RandomState(3)
        vals = rng.choice(["Alpha", "beta ", " Gamma", "DELTA"], n)
        return s.create_dataframe(pd.DataFrame({"s": vals, "i": range(n)}))

    def test_transform_chain_evaluates_over_dictionary(self):
        import spark_rapids_tpu.exprs.string_fns as SF
        calls = []
        orig = SF.Upper.eval_host
        def spy(self, batch):
            calls.append(batch.num_rows)
            return orig(self, batch)
        SF.Upper.eval_host = spy
        try:
            df = self._dict_df()
            out = df.select(
                F.upper(F.trim(F.col("s"))).alias("u")).to_pandas()
        finally:
            SF.Upper.eval_host = orig
        assert sorted(set(out["u"])) == ["ALPHA", "BETA", "DELTA", "GAMMA"]
        # evaluated over the 4-entry dictionary, not the 5000 rows
        assert calls and max(calls) <= 4, calls

    def test_dict_transform_matches_host_engine(self):
        n = 2000
        rng = __import__("numpy").random.RandomState(8)
        vals = [None if x == "N" else x
                for x in rng.choice(["aa:bb", "cc:dd", "N", "e:f"], n)]
        pdf = pd.DataFrame({"s": vals})
        s = tpu_session()
        from harness import cpu_session
        cols = [F.substring(F.col("s"), 1, 2).alias("sub"),
                F.regexp_replace(F.col("s"), ":", "-").alias("rr"),
                F.upper(F.col("s")).alias("up")]
        got = s.create_dataframe(pdf).select(*cols).to_pandas()
        want = cpu_session().create_dataframe(pdf).select(*cols).to_pandas()
        for c in ("sub", "rr", "up"):
            assert got[c].fillna("<N>").tolist() == \
                want[c].fillna("<N>").tolist(), c

    def test_transformed_dict_predicate_falls_back_to_mask(self):
        # after upper(), the dictionary is unsorted: a prefix predicate
        # (range form) must still be correct via the contiguity guard
        df = self._dict_df()
        out = (df.select(F.upper(F.col("s")).alias("u"), F.col("i"))
               .filter(F.startswith(F.col("u"), "B"))
               .to_pandas())
        assert set(out["u"]) == {"BETA "}

    def test_transformed_dict_sorts_and_merges_correctly(self):
        """upper() can merge ('Alpha','ALPHA ') and reorder entries: the
        transformed dictionary must be re-sorted + deduped with codes
        remapped, or device sorts/windows order by stale codes (r3
        review finding)."""
        pdf = pd.DataFrame(
            {"s": ["Banana", "apple", "APPLE", "cherry"] * 50})
        s = tpu_session()
        out = (s.create_dataframe(pdf)
               .select(F.upper(F.col("s")).alias("u"))
               .sort(F.col("u").asc())
               .to_pandas())
        assert out["u"].tolist() == (["APPLE"] * 100 + ["BANANA"] * 50
                                     + ["CHERRY"] * 50)
        # grouping merges the case-folded duplicates into ONE group
        g = (s.create_dataframe(pdf)
             .select(F.upper(F.col("s")).alias("u"))
             .group_by("u").agg(F.count_star().with_name("n"))
             .to_pandas().sort_values("u").reset_index(drop=True))
        assert g["u"].tolist() == ["APPLE", "BANANA", "CHERRY"]
        assert g["n"].tolist() == [100, 50, 50]


# ---------------------------------------------------------------------------
# Byte-rectangle device strings (r4: VERDICT #4 — high cardinality)
# ---------------------------------------------------------------------------

def _high_card_table(n=60000, card=30000, seed=7):
    import numpy as np
    import pyarrow as pa
    rng = np.random.RandomState(seed)
    pool = np.asarray([f"  Item-{i:06d}-{'x' * (i % 9)}  "
                       for i in range(card)], dtype=object)
    return pa.table({"s": pa.array(pool[rng.randint(0, card, n)]),
                     "v": pa.array(rng.uniform(0, 10, n))})


def test_rect_column_engages_at_high_cardinality():
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.columnar.strrect import ByteRectColumn
    b = ColumnarBatch.from_arrow(_high_card_table(20000, 15000))
    assert isinstance(b.columns[0], ByteRectColumn), type(b.columns[0])
    assert b.columns[0].ascii_only
    # exact string roundtrip through the rectangle
    got = b.to_arrow().column("s")
    want = _high_card_table(20000, 15000).column("s")
    assert got.to_pylist() == want.to_pylist()


def test_rect_transform_chain_differential():
    """upper(trim(s)) / substring / length / predicates over a rectangle
    column match the host engine exactly (high cardinality: the dict
    path is out of play)."""
    t = _high_card_table()

    def q(s):
        return (s.create_dataframe(t)
                .select(F.upper(F.trim(F.col("s"))).alias("u"),
                        F.substring(F.col("s"), 3, 6).alias("pre"),
                        F.length(F.col("s")).alias("ln"),
                        F.col("v")))
    assert_tpu_and_cpu_equal(q)


def test_rect_predicates_differential():
    t = _high_card_table(30000, 20000)

    def q(s):
        df = s.create_dataframe(t)
        return df.filter(F.col("s").contains("0123")) \
                 .select(F.col("s"), F.col("v"))
    assert_tpu_and_cpu_equal(q)


def test_rect_transform_used_on_device():
    """The chain must actually run on the rectangle (not host fallback)."""
    from harness import tpu_session
    from spark_rapids_tpu.columnar.strrect import ByteRectColumn
    s = tpu_session()
    df = (s.create_dataframe(_high_card_table(20000, 15000))
          .select(F.upper(F.trim(F.col("s"))).alias("u"), F.col("v")))
    phys = df._physical()
    batches = list(phys.execute(s.exec_context()))
    assert any(isinstance(b.columns[0], ByteRectColumn) for b in batches), \
        [type(b.columns[0]) for b in batches]


def test_rect_non_ascii_falls_back_to_host():
    import numpy as np
    import pyarrow as pa
    rng = np.random.RandomState(3)
    pool = np.asarray([f"wört-{i:05d}" for i in range(8000)], dtype=object)
    t = pa.table({"s": pa.array(pool[rng.randint(0, 8000, 16000)])})

    def q(s):
        return s.create_dataframe(t).select(
            F.upper(F.col("s")).alias("u"))
    assert_tpu_and_cpu_equal(q)


def test_rect_groupby_high_cardinality_differential():
    """The bench shape at high cardinality: group by TRANSFORMED rect
    strings — keys group on device via packed-word operands (r4
    VERDICT #4 'done' criterion path)."""
    t = _high_card_table(60000, 30000)

    def q(s):
        return (s.create_dataframe(t)
                .select(F.upper(F.trim(F.col("s"))).alias("u"),
                        F.substring(F.col("s"), 3, 6).alias("pre"),
                        F.col("v"))
                .group_by("u", "pre")
                .agg(F.sum(F.col("v")).with_name("sv"),
                     F.count_star().with_name("n")))
    assert_tpu_and_cpu_equal(q, approximate_float=True)


def test_rect_groupby_multibatch_differential():
    t = _high_card_table(60000, 25000)

    def q(s):
        return (s.create_dataframe(t, num_partitions=4)
                .select(F.upper(F.trim(F.col("s"))).alias("u"), F.col("v"))
                .group_by("u")
                .agg(F.sum(F.col("v")).with_name("sv"),
                     F.count_star().with_name("n"),
                     F.min(F.col("v")).with_name("mn")))
    assert_tpu_and_cpu_equal(q, approximate_float=True)


def test_rect_groupby_direct_column_with_nulls():
    import numpy as np
    import pyarrow as pa
    rng = np.random.RandomState(5)
    pool = np.asarray([f"key-{i:05d}" for i in range(9000)], dtype=object)
    vals = pool[rng.randint(0, 9000, 20000)].astype(object)
    vals[rng.rand(20000) < 0.05] = None
    t = pa.table({"s": pa.array(vals), "v": pa.array(rng.rand(20000))})

    def q(s):
        return (s.create_dataframe(t).group_by("s")
                .agg(F.sum(F.col("v")).with_name("sv"),
                     F.count_star().with_name("n")))
    assert_tpu_and_cpu_equal(q, approximate_float=True)


def test_rect_replace_pad_differential():
    """r5: StringReplace / Lpad / Rpad over rectangles (width growth,
    cyclic pad, truncation) match the host engine exactly."""
    t = _high_card_table(30000, 20000)

    def q(s):
        return (s.create_dataframe(t)
                .select(F.replace(F.col("s"), "Item", "Thing").alias("r1"),
                        F.replace(F.col("s"), "-", "").alias("r2"),
                        F.replace(F.col("s"), "x", "yz").alias("r3"),
                        F.lpad(F.trim(F.col("s")), 24, "*").alias("lp"),
                        F.rpad(F.trim(F.col("s")), 6, "ab").alias("rp"),
                        F.col("v")))
    assert_tpu_and_cpu_equal(q)


def test_rect_locate_instr_like_differential():
    t = _high_card_table(30000, 20000)

    def q(s):
        df = s.create_dataframe(t)
        return df.select(F.locate("-00", F.col("s")).alias("loc"),
                         F.instr(F.col("s"), "xx").alias("ins"),
                         F.col("s").like("%Item-0%").alias("lk1"),
                         F.col("s").like("  Item%").alias("lk2"),
                         F.col("s").like("%xx  ").alias("lk3"),
                         F.col("v"))
    assert_tpu_and_cpu_equal(q)


def test_rect_substring_index_reverse_differential():
    t = _high_card_table(30000, 20000)

    def q(s):
        df = s.create_dataframe(t)
        return df.select(
            F.substring_index(F.trim(F.col("s")), "-", 2).alias("p2"),
            F.substring_index(F.trim(F.col("s")), "-", -1).alias("m1"),
            F.reverse(F.trim(F.col("s"))).alias("rev"),
            F.col("v"))
    assert_tpu_and_cpu_equal(q)


def test_rect_new_ops_run_on_device():
    """The r5 ops must actually engage the rectangle kernel, not fall
    back to per-row host eval."""
    from harness import tpu_session
    s = tpu_session()
    df = (s.create_dataframe(_high_card_table(20000, 15000))
          .select(F.replace(F.col("s"), "Item", "I").alias("r"),
                  F.col("v")))
    exec_ = df._physical()
    node = exec_
    while node.children and not hasattr(node, "rect_chain"):
        node = node.children[0]
    assert getattr(node, "rect_chain", None), exec_.tree_string()


def test_rect_edgecases_empty_and_all_space():
    import pyarrow as pa
    vals = (["", "   ", "a", "-", "--", "a-b-c", "x" * 31, None,
             "ab-", "-ab", "a--b"] * 600)
    t = pa.table({"s": pa.array(vals + [f"u{i}" for i in range(9000)])})

    def q(s):
        df = s.create_dataframe(t)
        return df.select(
            F.replace(F.col("s"), "-", "=+").alias("r"),
            F.lpad(F.col("s"), 5).alias("lp"),
            F.rpad(F.col("s"), 3).alias("rp"),
            F.substring_index(F.col("s"), "-", 1).alias("s1"),
            F.substring_index(F.col("s"), "-", -2).alias("sm"),
            F.locate("-", F.col("s")).alias("lc"),
            F.reverse(F.col("s")).alias("rv"))
    assert_tpu_and_cpu_equal(q)


def test_rect_rlike_literal_routing_differential():
    """r5: RLIKE patterns that are plain (optionally anchored) literals
    run on the rectangle device path; real regexes stay host."""
    from spark_rapids_tpu.exprs.string_rect import (_rlike_literal_parts,
                                                    rect_supported_op)
    from spark_rapids_tpu.exprs import string_fns as SF
    assert _rlike_literal_parts("Item-00") == ("contains", "Item-00")
    assert _rlike_literal_parts("^Item") == ("startswith", "Item")
    assert _rlike_literal_parts("xx$") == ("endswith", "xx")
    assert _rlike_literal_parts("^ab$") == ("equals", "ab")
    assert _rlike_literal_parts("It.m") is None
    assert _rlike_literal_parts("a+") is None
    assert not rect_supported_op(SF.RLike(None, "a|b"))

    t = _high_card_table(25000, 18000)

    def q(s):
        df = s.create_dataframe(t)
        return df.select(F.rlike(F.col("s"), "Item-00").alias("r1"),
                         F.rlike(F.col("s"), "^  Item").alias("r2"),
                         F.rlike(F.col("s"), "xx  $").alias("r3"),
                         F.col("v"))
    assert_tpu_and_cpu_equal(q)


# ---------------------------------------------------------------------------
# =, <>, IN against string literals over a dictionary (PR 32): the match is
# computed once per distinct value, the batch stays on the device
# ---------------------------------------------------------------------------

_LITERAL_PREDICATES = {
    "eq": lambda c: c == "banana_002",
    "eq_flipped": lambda c: F.lit("banana_002") == c,
    "eq_absent": lambda c: c == "no such value",
    "ne": lambda c: c != "banana_002",
    "ne_absent": lambda c: c != "no such value",
    "in": lambda c: c.isin(["banana_002", "date_004", "no such value"]),
    "not_in": lambda c: ~c.isin(["banana_002", "date_004"]),
    "not_eq": lambda c: ~(c == "banana_002"),
    "mixed": lambda c: (c == "banana_002") | ((c != "date_004")
                                              & (F.col("v") < F.lit(20))),
}


@pytest.mark.parametrize("pred", sorted(_LITERAL_PREDICATES))
def test_dict_filter_literal_comparisons(pred):
    """NULL strings match nothing, under NOT as well; a literal the
    dictionary does not hold matches no row (=, IN) or every non-NULL one
    (<>)."""
    t = _str_table()

    def q(s):
        return (s.create_dataframe(t)
                .filter(_LITERAL_PREDICATES[pred](F.col("s")))
                .agg(F.count_star().with_name("c"),
                     F.sum(F.col("v")).with_name("sv")))
    s = tpu_session()
    tree = q(s)._physical().tree_string()
    assert not [l for l in tree.splitlines()
                if "Cpu" in l or l.strip().startswith("!")], tree
    got = assert_tpu_and_cpu_equal(q)
    if pred == "eq_absent":
        assert got["c"].tolist() == [0]
    if pred == "ne_absent":
        assert got["c"].tolist() == [t.column("s").drop_null().__len__()]


def test_dict_filter_literal_comparison_is_tagged_and_placed_on_device():
    t = _str_table()
    s = tpu_session({"spark.rapids.tpu.sql.optimizer.enabled": False})
    df = s.create_dataframe(t).filter(F.col("s") == "banana_002")
    out = df.collect_arrow()
    assert set(out.column("s").to_pylist()) == {"banana_002"}
    assert s.last_placement == "device"
    assert s.last_placement_report["codes"] == {"EXPR_DICT_EVAL": 1}


def test_in_list_with_a_null_keeps_the_host_path():
    """x IN ('a', NULL) is NULL, not false, where x is not 'a': a mask
    over the dictionary cannot say that, so the predicate is not taken."""
    t = _str_table()

    def q(s):
        return (s.create_dataframe(t)
                .filter(~F.col("s").isin(["banana_002", None])))
    assert "CpuFilter" in q(tpu_session())._physical().tree_string()
    assert len(assert_tpu_and_cpu_equal(q)) == 0
