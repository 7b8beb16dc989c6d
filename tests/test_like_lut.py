"""The LIKE matcher (`exec/expr_compile.py`): `like_match` over an array of
strings (as numpy unicode) and `like_lut`, a dictionary's table of
verdicts, an entry each, matched by Arrow's RE2 over the whole dictionary
and memoized on the DictInfo (what the device tier gathers from). It gives
what SQL's LIKE gives — held here against a plain Python matcher, character
by character — for wildcards, regex metacharacters, newlines, Unicode and
ILIKE."""
import numpy as np
import pytest

from igloo_tpu.exec.batch import DictInfo
from igloo_tpu.exec.expr_compile import like_lut, like_match

VALUES = ["abc", "a.c", "a\nb", "PROMO BRUSHED", "promo x", "x%y", "a_b", "",
          "special requests", "special packages. requests", "[x]*(y)",
          "tab\there", "naïve Ünïcode", "a\\b", "$1.00^", "ends\n",
          "ss", "ß"]


def like(value: str, pattern: str, case_insensitive: bool) -> bool:
    """SQL LIKE by recursion over the pattern: `%` any run, `_` one
    character, every other character itself."""
    if case_insensitive:
        value, pattern = value.lower(), pattern.lower()

    def match(i: int, j: int) -> bool:
        if j == len(pattern):
            return i == len(value)
        if pattern[j] == "%":
            return any(match(k, j + 1) for k in range(i, len(value) + 1))
        if i < len(value) and pattern[j] in ("_", value[i]):
            return match(i + 1, j + 1)
        return False
    return match(0, 0)


@pytest.mark.parametrize("entry", [
    lambda pattern, ci: like_lut(DictInfo.from_values(VALUES), pattern, ci),
    lambda pattern, ci: like_match(np.asarray(VALUES, dtype=str), pattern, ci),
], ids=["dictionary", "values"])
@pytest.mark.parametrize("case_insensitive", [False, True])
@pytest.mark.parametrize("pattern", [
    "%", "", "_", "a%", "a_c", "a.c", "%special%requests%", "PROMO%",
    "x\\%y", "%[%]%", "a\nb", "a_b", "%ü%", "naïve%", "a\\%", "$%^",
    "%\t%", "ends", "ends_", "%.%", "%(y)"])
def test_verdicts_are_sql_like(pattern, case_insensitive, entry):
    got = entry(pattern, case_insensitive)
    assert got.dtype == np.bool_ and got.shape == (len(VALUES),)
    want = [like(v, pattern, case_insensitive) for v in VALUES]
    assert got.tolist() == want


def test_one_table_per_dictionary_pattern_and_case():
    d = DictInfo.from_values(VALUES)
    first = like_lut(d, "a%", False)
    assert like_lut(d, "a%", False) is first
    assert like_lut(d, "a%", True) is not first
    other = DictInfo.from_values(VALUES[::-1])
    assert like_lut(other, "a%", False).tolist() == first.tolist()[::-1]


def test_values_that_are_not_strings_are_matched_as_text():
    d = DictInfo(np.asarray([1, "1x", 2.5], dtype=object),
                 np.zeros(3, np.uint64), np.zeros(3, np.uint64))
    assert like_lut(d, "1%", False).tolist() == [True, True, False]
