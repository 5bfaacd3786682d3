"""String expressions (ref stringFunctions.scala, 2,377 LoC).

Strings are host-resident (Arrow) in round 1 — every expression here is a
vectorized Arrow kernel, honestly tagged host-only so the planner records
the fallback (the reference's TypeSig machinery makes exactly this per-type
fallback cheap, SURVEY.md section 7 hard-part #2). Numeric outputs (length,
locate, comparisons) are H2D'd by the project exec so downstream compute
stays on the TPU. Regex expressions go through the Java->Python transpiler
(regex_transpiler.py) and REJECT patterns with divergent semantics.
"""
from __future__ import annotations

from typing import Optional

from ..types import (BOOL, INT32, STRING, Schema, TypeSig, TypeEnum)
from .base import Expression, Unsupported

__all__ = ["Length", "Upper", "Lower", "Substring", "ConcatStrings",
           "Contains", "StartsWith", "EndsWith", "Like", "RLike",
           "RegExpReplace", "RegExpExtract", "StringTrim", "StringTrimLeft",
           "StringTrimRight", "StringReplace", "StringLocate", "Lpad",
           "Rpad", "Reverse", "StringRepeat", "InitCap", "StringSplit",
           "SubstringIndex", "Ascii", "Chr", "BitLength", "OctetLength",
           "RegExpExtractAll", "Conv",
           "StringInstr", "StringTranslate", "ConcatWs", "FormatNumber"]

_str_sig = TypeSig([TypeEnum.STRING])


class _HostStringExpr(Expression):
    """Base: runs on host Arrow; device tagging returns an explicit reason
    so explain output mirrors the reference's NOT_ON_GPU messages.

    ``dict_transform = True`` marks VALUE-WISE string->string transforms:
    over a dictionary-coded column the project exec evaluates them ONCE
    per distinct dictionary entry and re-encodes — row data never leaves
    the device (the O(dict) transform generalization of the r2 predicate
    trick; ref stringFunctions.scala device kernels)."""

    #: subclasses that map each string value independently set True
    dict_transform = False

    def device_unsupported_reason(self, schema: Schema) -> Optional[str]:
        return f"{type(self).__name__}: string expressions run on host"

    def key(self):
        kids = ",".join(c.key() for c in self.children)
        return f"{type(self).__name__}({kids})"


class Length(_HostStringExpr):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return INT32

    def eval_host(self, batch):
        import pyarrow as pa
        import pyarrow.compute as pc
        return pc.cast(pc.utf8_length(self.children[0].eval_host(batch)),
                       pa.int32())


class Upper(_HostStringExpr):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    dict_transform = True
    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow.compute as pc
        return pc.utf8_upper(self.children[0].eval_host(batch))


class Lower(_HostStringExpr):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    dict_transform = True
    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow.compute as pc
        return pc.utf8_lower(self.children[0].eval_host(batch))


class Substring(_HostStringExpr):
    """Spark substring: 1-based, pos 0 treated as 1, negative from end."""
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    dict_transform = True

    def __init__(self, child, pos: int, length: Optional[int] = None):
        self.children = [child]
        self.pos = pos
        self.length = length

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow.compute as pc
        arr = self.children[0].eval_host(batch)
        if self.length is not None and self.length <= 0:
            return pc.utf8_slice_codeunits(arr, 0, 0)  # "" (nulls preserved)
        start = self.pos - 1 if self.pos > 0 else self.pos  # 0 acts like 1
        if self.length is None:
            stop = None
        elif start >= 0:
            stop = start + self.length
        else:  # negative start: stop only if it stays negative
            stop = start + self.length if start + self.length < 0 else None
        return pc.utf8_slice_codeunits(arr, start, stop)

    def key(self):
        return (f"substr({self.children[0].key()},{self.pos},"
                f"{self.length})")


class ConcatStrings(_HostStringExpr):
    """concat(s1, s2, ...): null if any input null (Spark concat)."""

    def __init__(self, *children):
        self.children = list(children)

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow as pa
        import pyarrow.compute as pc
        arrs = [c.eval_host(batch) for c in self.children]
        # unify string width (pandas3 produces large_string)
        target = pa.large_string() if any(
            pa.types.is_large_string(a.type) for a in arrs) else pa.string()
        arrs = [pc.cast(a, target) for a in arrs]
        return pc.binary_join_element_wise(
            *arrs, pa.scalar("", type=target), null_handling="emit_null")


def _transpile_with_fallback(pattern: str, mode: str):
    """(re2_regex, py_regex): exactly one is non-None. RE2 (pyarrow's
    vectorized kernels) is the fast path; patterns it cannot run
    (lookaround, backrefs, mode-dependent anchors) transpile for the
    Python-re row loop instead — the analog of the reference's CPU
    fallback, with Java semantics restored per target."""
    from .regex_transpiler import RegexUnsupported, transpile_java_regex
    try:
        return transpile_java_regex(pattern, target="re2",
                                    mode=mode), None
    except RegexUnsupported:
        return None, transpile_java_regex(pattern, target="python")


def _py_row_map(arr, fn, out_type):
    """Per-row Python fallback over an Arrow array; nulls pass through."""
    import pyarrow as pa
    return pa.array([None if v is None else fn(v) for v in arr.to_pylist()],
                    type=out_type)


class _PatternPredicate(_HostStringExpr):
    """String->bool predicate. ``host_mask`` is the single definition of
    the match, shared by row-wise host evaluation AND the dictionary
    path: over dict-coded device columns the predicate evaluates ONCE per
    distinct value and broadcasts through the codes on device
    (exprs/compiler.py DictFilterEvaluator; ref stringFunctions.scala
    device kernels — this is the O(dict) TPU equivalent)."""

    #: "range": on the SORTED dictionary the matching codes are one
    #: contiguous span -> gather-free (codes >= lo) & (codes < hi);
    #: "mask": arbitrary match set -> one small-table lookup
    dict_form = "mask"

    def __init__(self, child, pattern: str):
        self.children = [child]
        self.pattern = pattern

    def data_type(self, schema):
        return BOOL

    def dict_column(self, schema):
        """The plain STRING column the pattern is matched against, or
        None (exprs/compiler.py build_dict_filter)."""
        from .comparison import _plain_string_column
        return _plain_string_column(self.children[0], schema)

    def host_mask(self, arr):
        raise NotImplementedError

    def eval_host(self, batch):
        return self.host_mask(self.children[0].eval_host(batch))

    def key(self):
        return (f"{type(self).__name__}({self.children[0].key()},"
                f"{self.pattern!r})")


class Contains(_PatternPredicate):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    def host_mask(self, arr):
        import pyarrow.compute as pc
        return pc.match_substring(arr, self.pattern)


class StartsWith(_PatternPredicate):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    dict_form = "range"     # prefix match == code range on a sorted dict

    def host_mask(self, arr):
        import pyarrow.compute as pc
        return pc.starts_with(arr, self.pattern)


class EndsWith(_PatternPredicate):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    def host_mask(self, arr):
        import pyarrow.compute as pc
        return pc.ends_with(arr, self.pattern)


class Like(_PatternPredicate):
    """SQL LIKE (ref GpuLike)."""
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True

    def __init__(self, child, pattern: str, escape: str = "\\"):
        super().__init__(child, pattern)
        self.escape = escape
        from .regex_transpiler import sql_like_to_regex
        self._regex = sql_like_to_regex(pattern, escape)

    def key(self):
        return (f"Like({self.children[0].key()},{self.pattern!r},"
                f"{self.escape!r})")

    def host_mask(self, arr):
        import pyarrow.compute as pc
        return pc.match_substring_regex(arr, self._regex)


class RLike(_PatternPredicate):
    """Java-regex RLIKE through the transpiler (ref GpuRLike +
    CudfRegexTranspiler)."""
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: literal / anchored-literal patterns only — see
    #: _rlike_literal_parts)
    rect_device = True

    def __init__(self, child, pattern: str):
        super().__init__(child, pattern)
        self._regex, self._pyregex = _transpile_with_fallback(pattern,
                                                              "find")

    def host_mask(self, arr):
        import pyarrow.compute as pc
        if self._regex is not None:
            return pc.match_substring_regex(arr, self._regex)
        import re
        import pyarrow as pa
        rx = re.compile(self._pyregex)
        return _py_row_map(arr, lambda v: rx.search(v) is not None,
                           pa.bool_())


class RegExpReplace(_HostStringExpr):
    dict_transform = True
    def __init__(self, child, pattern: str, replacement: str):
        self.children = [child]
        self.pattern = pattern
        self.replacement = replacement
        self._regex, self._pyregex = _transpile_with_fallback(pattern,
                                                              "replace")

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import re
        arr = self.children[0].eval_host(batch)
        # Java $1 backrefs -> \1 (same spelling in RE2 and Python re)
        repl = re.sub(r"\$(\d)", r"\\\1", self.replacement)
        if self._regex is not None:
            import pyarrow.compute as pc
            return pc.replace_substring_regex(arr, self._regex, repl)
        import pyarrow as pa
        rx = re.compile(self._pyregex)
        return _py_row_map(arr, lambda v: rx.sub(repl, v), pa.string())

    def key(self):
        return (f"regexp_replace({self.children[0].key()},"
                f"{self.pattern!r},{self.replacement!r})")


class RegExpExtract(_HostStringExpr):
    def __init__(self, child, pattern: str, group: int = 1):
        self.children = [child]
        self.pattern = pattern
        self.group = group
        from .regex_transpiler import transpile_java_regex
        self._regex = transpile_java_regex(pattern)

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import re
        import pyarrow as pa
        arr = self.children[0].eval_host(batch)
        rx = re.compile(self._regex)
        out = []
        for v in arr.to_pylist():
            if v is None:
                out.append(None)
            else:
                m = rx.search(v)
                out.append("" if m is None else (m.group(self.group) or ""))
        return pa.array(out, type=pa.string())

    def key(self):
        return (f"regexp_extract({self.children[0].key()},"
                f"{self.pattern!r},{self.group})")


class _TrimBase(_HostStringExpr):
    """Default TRIM removes ONLY the space character 0x20 — NOT tabs or
    newlines (Spark semantics, SPARK-17299; r5 ground-truth finding:
    utf8_trim_whitespace silently stripped all whitespace)."""
    dict_transform = True
    pc_fn = "utf8_trim"

    def __init__(self, child, chars: Optional[str] = None):
        self.children = [child]
        self.chars = chars

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow.compute as pc
        arr = self.children[0].eval_host(batch)
        return getattr(pc, self.pc_fn)(
            arr, characters=self.chars if self.chars is not None else " ")


class RegExpExtractAll(_HostStringExpr):
    """regexp_extract_all(str, regex, group) -> array<string> (ref
    GpuRegExpExtractAll via the transpiler; host-only nested output)."""

    def __init__(self, child, pattern: str, group: int = 1):
        self.children = [child]
        self.pattern = pattern
        self.group = int(group)
        # the eval is a python row loop: always transpile for python-re
        # (the re2 dialect is only valid inside pyarrow pc.* kernels)
        from .regex_transpiler import transpile_java_regex
        self._pyregex = transpile_java_regex(pattern, target="python")

    def data_type(self, schema):
        from ..types import ArrayType
        return ArrayType(STRING)

    def eval_host(self, batch):
        import re as _re
        import pyarrow as pa
        rx = _re.compile(self._pyregex)
        arr = self.children[0].eval_host(batch)
        out = []
        for v in arr.to_pylist():
            if v is None:
                out.append(None)
                continue
            vals = []
            for m in rx.finditer(v):
                g = m.group(self.group) if self.group else m.group(0)
                vals.append("" if g is None else g)
            out.append(vals)
        return pa.array(out, type=pa.list_(pa.string()))

    def key(self):
        return (f"regexp_extract_all({self.children[0].key()},"
                f"{self.pattern!r},{self.group})")


class Conv(_HostStringExpr):
    """conv(num_str, from_base, to_base): base conversion with Java
    semantics — invalid digits truncate the parse, empty parse -> NULL,
    negative to_base keeps the sign, uppercase output (ref GpuConv)."""

    def __init__(self, child, from_base: int, to_base: int):
        self.children = [child]
        self.from_base = int(from_base)
        self.to_base = int(to_base)

    def data_type(self, schema):
        return STRING

    def _convert(self, v: str):
        fb, tb = self.from_base, abs(self.to_base)
        if not (2 <= fb <= 36 and 2 <= tb <= 36):
            return None
        v = v.strip()
        neg = v.startswith("-")
        if neg:
            v = v[1:]
        digits = "0123456789abcdefghijklmnopqrstuvwxyz"[:fb]
        acc = 0
        seen = False
        for ch in v.lower():
            d = digits.find(ch)
            if d < 0:
                break
            acc = acc * fb + d
            seen = True
        if not seen:
            return None
        acc = min(acc, (1 << 64) - 1)     # Java clamps at unsigned max
        # two's-complement 64-bit value (modulo keeps '-0' at 0)
        v = ((1 << 64) - acc) % (1 << 64) if neg else acc
        if self.to_base > 0:
            neg_out, mag = False, v       # printed UNSIGNED
        else:
            # negative to_base prints the value as a SIGNED long
            sval = v - (1 << 64) if v >= (1 << 63) else v
            neg_out, mag = sval < 0, abs(sval)
        out_digits = "0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
        if mag == 0:
            return "0"
        out = []
        n = mag
        while n:
            out.append(out_digits[n % tb])
            n //= tb
        body = "".join(reversed(out))
        return ("-" + body) if neg_out else body

    def eval_host(self, batch):
        import pyarrow as pa
        arr = self.children[0].eval_host(batch)
        return _py_row_map(arr, self._convert, pa.string())

    def key(self):
        return (f"conv({self.children[0].key()},{self.from_base},"
                f"{self.to_base})")


class StringTrim(_TrimBase):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    pc_fn = "utf8_trim"


class StringTrimLeft(_TrimBase):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    pc_fn = "utf8_ltrim"


class StringTrimRight(_TrimBase):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    pc_fn = "utf8_rtrim"


class StringReplace(_HostStringExpr):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    dict_transform = True
    def __init__(self, child, search: str, replace: str):
        self.children = [child]
        self.search = search
        self.replace = replace

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow.compute as pc
        return pc.replace_substring(self.children[0].eval_host(batch),
                                    self.search, self.replace)

    def key(self):
        return (f"replace({self.children[0].key()},{self.search!r},"
                f"{self.replace!r})")


class StringLocate(_HostStringExpr):
    """locate(substr, str): 1-based, 0 if absent (ref GpuStringLocate)."""
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True

    def __init__(self, substr: str, child):
        self.children = [child]
        self.substr = substr

    def data_type(self, schema):
        return INT32

    def eval_host(self, batch):
        import pyarrow as pa
        import pyarrow.compute as pc
        arr = self.children[0].eval_host(batch)
        # find_substring returns BYTE offsets; Spark wants 1-based CHARACTER
        # position -> measure the prefix before the first occurrence
        parts = pc.split_pattern(arr, self.substr, max_splits=1)
        prefix_len = pc.utf8_length(pc.list_element(parts, 0))
        found = pc.match_substring(arr, self.substr)
        pos = pc.if_else(found, pc.add(prefix_len, 1),
                         pc.cast(0, prefix_len.type))
        return pc.cast(pos, pa.int32())

    def key(self):
        return f"locate({self.substr!r},{self.children[0].key()})"


class Lpad(_HostStringExpr):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    dict_transform = True
    def __init__(self, child, length: int, pad: str = " "):
        self.children = [child]
        self.length = length
        self.pad = pad

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow.compute as pc
        arr = self.children[0].eval_host(batch)
        if len(self.pad) == 1:
            padded = pc.utf8_lpad(arr, self.length, padding=self.pad)
        else:
            # Arrow pads single codepoints only; Spark pads cyclically
            import pyarrow as pa
            L, p = self.length, self.pad
            padded = _py_row_map(
                arr, lambda v: ((p * L)[:max(L - len(v), 0)] + v),
                pa.string())
        # Spark truncates to length
        return pc.utf8_slice_codeunits(padded, 0, self.length)

    def key(self):
        return f"lpad({self.children[0].key()},{self.length},{self.pad!r})"


class Rpad(Lpad):
    def eval_host(self, batch):
        import pyarrow.compute as pc
        arr = self.children[0].eval_host(batch)
        if len(self.pad) == 1:
            padded = pc.utf8_rpad(arr, self.length, padding=self.pad)
        else:
            import pyarrow as pa
            L, p = self.length, self.pad
            padded = _py_row_map(
                arr, lambda v: v + (p * L)[:max(L - len(v), 0)],
                pa.string())
        return pc.utf8_slice_codeunits(padded, 0, self.length)

    def key(self):
        return f"rpad({self.children[0].key()},{self.length},{self.pad!r})"


class Reverse(_HostStringExpr):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    dict_transform = True
    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow.compute as pc
        return pc.utf8_reverse(self.children[0].eval_host(batch))


class StringRepeat(_HostStringExpr):
    dict_transform = True
    def __init__(self, child, times: int):
        self.children = [child]
        self.times = times

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow.compute as pc
        # Spark: repeat with n <= 0 yields '' (arrow rejects negatives)
        return pc.binary_repeat(self.children[0].eval_host(batch),
                                max(self.times, 0))

    def key(self):
        return f"repeat({self.children[0].key()},{self.times})"


class InitCap(_HostStringExpr):
    """initcap: Spark capitalizes the first letter of EVERY
    space-separated word and lowercases the rest ('hELLO wORLD' ->
    'Hello World'); arrow's utf8_capitalize only title-cases the first
    character of the whole string (r5 ground-truth finding)."""
    dict_transform = True

    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return STRING

    @staticmethod
    def _initcap(v: str) -> str:
        return " ".join(w[:1].upper() + w[1:].lower()
                        for w in v.split(" "))

    def eval_host(self, batch):
        import pyarrow as pa
        return _py_row_map(self.children[0].eval_host(batch),
                           self._initcap, pa.string())


class StringSplit(_HostStringExpr):
    """split(str, java_regex) -> array<string> (host-only nested output)."""

    def __init__(self, child, pattern: str, limit: int = -1):
        self.children = [child]
        self.pattern = pattern
        self.limit = limit
        self._regex, self._pyregex = _transpile_with_fallback(pattern,
                                                              "split")

    def data_type(self, schema):
        from ..types import ArrayType
        return ArrayType(STRING)

    @staticmethod
    def _strip_trailing_empties(list_arr):
        """Spark/Java limit=0: unlimited splits, then trailing empty
        strings removed (Pattern.split)."""
        import pyarrow as pa
        out = []
        for parts in list_arr.to_pylist():
            if parts is None:
                out.append(None)
                continue
            while parts and parts[-1] == "":
                parts.pop()
            out.append(parts)
        return pa.array(out, type=pa.list_(pa.string()))

    def eval_host(self, batch):
        import pyarrow as pa
        arr = self.children[0].eval_host(batch)
        lim = self.limit
        if self._regex is not None:
            import pyarrow.compute as pc
            kwargs = {} if lim <= 0 else {"max_splits": lim - 1}
            split = pc.split_pattern_regex(arr, self._regex, **kwargs)
            return self._strip_trailing_empties(split) if lim == 0 \
                else split
        import re
        rx = re.compile(self._pyregex)

        def split_one(v):
            # Spark limit (Java Pattern.split): >0 = at most `limit`
            # elements; 0 = unlimited + trailing empties removed; <0 =
            # unlimited keeping them. Python re.split's maxsplit inverts
            # the special values (0 = unlimited, negative = no splits),
            # so neither passes through directly.
            if lim == 1:
                return [v]                      # no splits at all
            parts = rx.split(v, 0 if lim <= 0 else lim - 1)
            if lim == 0:
                while parts and parts[-1] == "":
                    parts.pop()
            return parts
        return _py_row_map(arr, split_one, pa.list_(pa.string()))

    def key(self):
        return f"split({self.children[0].key()},{self.pattern!r})"


class SubstringIndex(_HostStringExpr):
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True
    dict_transform = True
    """substring_index(str, delim, count) (ref GpuSubstringIndexUtils JNI)."""

    def __init__(self, child, delim: str, count: int):
        self.children = [child]
        self.delim = delim
        self.count = count

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow as pa
        arr = self.children[0].eval_host(batch)
        out = []
        for v in arr.to_pylist():
            if v is None:
                out.append(None)
            elif self.count > 0:
                out.append(self.delim.join(v.split(self.delim)[:self.count]))
            elif self.count < 0:
                out.append(self.delim.join(v.split(self.delim)[self.count:]))
            else:
                out.append("")
        return pa.array(out, type=pa.string())

    def key(self):
        return (f"substring_index({self.children[0].key()},"
                f"{self.delim!r},{self.count})")


class ParseUrl(_HostStringExpr):
    """parse_url(url, part[, key]) (ref ParseURI JNI: GpuParseUrl).
    Parts: PROTOCOL, HOST, PATH, QUERY, REF, AUTHORITY, FILE, USERINFO;
    QUERY with a key extracts that query parameter."""

    PARTS = ("PROTOCOL", "HOST", "PATH", "QUERY", "REF", "AUTHORITY",
             "FILE", "USERINFO")

    def __init__(self, child, part: str, query_key=None):
        self.children = [child]
        self.part = part.upper()
        self.query_key = query_key

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow as pa
        from urllib.parse import urlparse
        arr = self.children[0].eval_host(batch)
        out = []
        for v in arr.to_pylist():
            if v is None:
                out.append(None)
                continue
            try:
                u = urlparse(v)
            except ValueError:
                out.append(None)
                continue
            # Spark (java.net.URI) returns NULL for every part of an
            # unparseable URL: require a scheme with an authority or
            # opaque part
            if not u.scheme or (not u.netloc and not u.path):
                out.append(None)
                continue
            if self.part == "PROTOCOL":
                r = u.scheme or None
            elif self.part == "HOST":
                # preserve case (u.hostname lowercases, Spark does not):
                # strip userinfo and port from the raw netloc
                h = u.netloc.rsplit("@", 1)[-1]
                if h.startswith("["):            # [ipv6]:port
                    r = h.split("]")[0] + "]" if "]" in h else h
                else:
                    r = h.split(":", 1)[0] or None
            elif self.part == "PATH":
                r = u.path or None
            elif self.part == "QUERY":
                r = u.query or None
                if r is not None and self.query_key is not None:
                    # RAW parameter value (Spark does not percent-decode)
                    r = None
                    for kv in u.query.split("&"):
                        k, _, val = kv.partition("=")
                        if k == self.query_key:
                            r = val
                            break
            elif self.part == "REF":
                r = u.fragment or None
            elif self.part == "AUTHORITY":
                r = u.netloc or None
            elif self.part == "FILE":
                r = (u.path + ("?" + u.query if u.query else "")) or None
            elif self.part == "USERINFO":
                r = u.netloc.rsplit("@", 1)[0] if "@" in u.netloc else None
            else:
                r = None
            out.append(r)
        return pa.array(out, type=pa.string())

    def key(self):
        return (f"parse_url({self.children[0].key()},{self.part},"
                f"{self.query_key!r})")


class Ascii(_HostStringExpr):
    """ascii(s): code point of the first character, 0 for '' (ref
    GpuAscii in stringFunctions.scala)."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return INT32

    def eval_host(self, batch):
        import pyarrow as pa
        vals = self.children[0].eval_host(batch).to_pylist()
        return pa.array([None if s is None else (ord(s[0]) if s else 0)
                         for s in vals], type=pa.int32())


class Chr(_HostStringExpr):
    """chr(n): character for code point n % 256 like Spark (0 -> '')."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow as pa
        vals = self.children[0].eval_host(batch).to_pylist()
        out = []
        for n in vals:
            if n is None:
                out.append(None)
            else:
                m = int(n) & 0xFF if int(n) >= 0 else 0
                out.append("" if m == 0 else chr(m))
        return pa.array(out, type=pa.string())


class BitLength(_HostStringExpr):
    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return INT32

    def eval_host(self, batch):
        import pyarrow as pa
        import pyarrow.compute as pc
        b = pc.binary_length(pc.cast(self.children[0].eval_host(batch),
                                     pa.binary()))
        return pc.cast(pc.multiply(b, pa.scalar(8)), pa.int32())


class OctetLength(_HostStringExpr):
    def __init__(self, child):
        self.children = [child]

    def data_type(self, schema):
        return INT32

    def eval_host(self, batch):
        import pyarrow as pa
        import pyarrow.compute as pc
        return pc.cast(pc.binary_length(
            pc.cast(self.children[0].eval_host(batch), pa.binary())),
            pa.int32())


class StringInstr(_HostStringExpr):
    """instr(str, substr): 1-based first occurrence, 0 if absent (ref
    GpuStringInstr — locate with fixed start=1)."""
    #: device byte-rectangle kernel available (exprs/string_rect.py;
    #: ASCII-gated, see rect_supported_op for per-instance conditions)
    rect_device = True

    def __init__(self, child, substr):
        self.children = [child, substr]

    def data_type(self, schema):
        return INT32

    def eval_host(self, batch):
        import pyarrow as pa
        s = self.children[0].eval_host(batch).to_pylist()
        sub = self.children[1].eval_host(batch).to_pylist()
        out = [None if a is None or b is None else a.find(b) + 1
               for a, b in zip(s, sub)]
        return pa.array(out, type=pa.int32())


class StringTranslate(_HostStringExpr):
    """translate(s, from, to): per-character mapping; chars beyond
    len(to) are deleted (ref GpuStringTranslate)."""

    dict_transform = True

    def __init__(self, child, src, dst):
        self.children = [child, src, dst]

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow as pa
        s = self.children[0].eval_host(batch).to_pylist()
        f = self.children[1].eval_host(batch).to_pylist()
        t = self.children[2].eval_host(batch).to_pylist()
        out = []
        for a, ff, tt in zip(s, f, t):
            if a is None or ff is None or tt is None:
                out.append(None)
                continue
            table = {}
            for i, ch in enumerate(ff):
                if ord(ch) not in table:   # first occurrence wins (Spark)
                    table[ord(ch)] = tt[i] if i < len(tt) else None
            out.append(a.translate(table))
        return pa.array(out, type=pa.string())


class ConcatWs(_HostStringExpr):
    """concat_ws(sep, args...): NULL args are skipped (unlike concat);
    NULL separator -> NULL (ref GpuConcatWs)."""

    def __init__(self, sep, *children):
        self.children = [sep] + list(children)

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow as pa
        sep = self.children[0].eval_host(batch).to_pylist()
        cols = [c.eval_host(batch).to_pylist() for c in self.children[1:]]
        out = []
        for i, sp in enumerate(sep):
            if sp is None:
                out.append(None)
                continue
            parts = []
            for col in cols:
                v = col[i]
                if v is None:
                    continue
                if isinstance(v, list):
                    parts.extend(str(x) for x in v if x is not None)
                else:
                    parts.append(str(v))
            out.append(sp.join(parts))
        return pa.array(out, type=pa.string())


class FormatNumber(_HostStringExpr):
    """format_number(x, d): thousands separators + d decimal places,
    HALF_EVEN like java.text.DecimalFormat (ref GpuFormatNumber)."""

    def __init__(self, child, decimals):
        self.children = [child, decimals]

    def data_type(self, schema):
        return STRING

    def eval_host(self, batch):
        import pyarrow as pa
        vals = self.children[0].eval_host(batch).to_pylist()
        decs = self.children[1].eval_host(batch).to_pylist()
        out = []
        for v, d in zip(vals, decs):
            if v is None or d is None or d < 0:
                out.append(None)
                continue
            out.append(f"{v:,.{int(d)}f}")
        return pa.array(out, type=pa.string())
