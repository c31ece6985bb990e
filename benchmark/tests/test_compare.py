"""compare.py as plain functions: alignment, per-key order, strangers,
float limits and non-finite values."""
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compare import (compare_rows, key_ids, out_of_order,  # noqa: E402
                     stable_order, verdict)

KEYS = np.asarray([f"g{i}" for i in range(6)], object)
SPEC = {"align": ["sym"], "exact": ["__ts", "sym", "n"],
        "float": {"total": 1e-5}}


def _tables():
    ids = np.asarray([0, 1, 0, 2, 1, 0])
    ref = {"__ts": np.arange(6) + 100, "sym": ids,
           "n": np.asarray([1, 1, 2, 1, 2, 3]),
           "total": np.asarray([10.0, 20.0, 30.0, 5.0, 50.0, 60.0])}
    ref = {k: v[np.argsort(ids, kind="stable")] for k, v in ref.items()}
    served = {"__ts": np.arange(6) + 100, "sym": KEYS[ids],
              "n": np.asarray([1, 1, 2, 1, 2, 3]),
              "total": np.asarray([10.0, 20.0, 30.0, 5.0, 50.0, 60.0])}
    return served, ref


def test_equal_tables_are_correct_and_key_ids_map_strangers_to_minus_one():
    served, ref = _tables()
    assert verdict(compare_rows(served, ref, SPEC, {"sym": KEYS}))
    assert list(key_ids(np.asarray(["g2", "zz"], object), KEYS)) == [2, -1]


def test_a_key_rows_out_of_order_a_stranger_and_a_float_off_all_fail():
    served, ref = _tables()
    swapped = {k: v.copy() for k, v in served.items()}
    for k in swapped:                       # key g0's first two rows
        swapped[k][[0, 2]] = swapped[k][[2, 0]]
    assert compare_rows(swapped, ref, SPEC, {"sym": KEYS})[
        "rows_unmatched"]["value"] == 2
    stranger = dict(served, sym=np.where(np.arange(6) == 3, "zz",
                                         served["sym"]))
    assert not verdict(compare_rows(stranger, ref, SPEC, {"sym": KEYS}))
    off = dict(served, total=served["total"] * (1 + 1e-4))
    checks = compare_rows(off, ref, SPEC, {"sym": KEYS})
    assert checks["rows_unmatched"]["value"] == 0 and not verdict(checks)
    nan = dict(served, total=np.where(np.arange(6) == 1, np.nan,
                                      served["total"]))
    assert compare_rows(nan, ref, SPEC, {"sym": KEYS})[
        "relerr_total"]["value"] == 1e30
    short = {k: v[:-1] for k, v in served.items()}
    assert compare_rows(short, ref, SPEC, {"sym": KEYS})[
        "rows_unmatched"]["value"] >= 1
    empty = {k: v[:0] for k, v in served.items()}
    assert not verdict(compare_rows(empty, {k: v[:0] for k, v in ref.items()},
                                    SPEC, {"sym": KEYS}))


def test_out_of_order_counts_rows_delivered_after_a_later_one_of_their_key():
    served = {"__q": np.asarray([0, 0, 1, 0, 1, 0]),
              "sym": np.asarray([3, 3, 3, 4, 3, 3]),
              "__ts": np.asarray([10, 12, 5, 1, 5, 11])}
    assert out_of_order(served, ["__q", "sym"], "__ts") == 1
    assert out_of_order({k: v[:5] for k, v in served.items()},
                        ["__q", "sym"], "__ts") == 0
    spec = {"align": ["__q", "sym", "__ts"], "exact": ["__q", "sym", "__ts"],
            "float": {}, "ordered": {"within": ["__q", "sym"], "by": "__ts"}}
    checks = compare_rows(served, served, spec)
    assert checks["rows_unmatched"]["value"] == 0
    assert checks["rows_out_of_order"]["value"] == 1 and not verdict(checks)


def test_stable_order_packs_integer_columns_and_keeps_arrival_order():
    rng = np.random.default_rng(3)
    tab = {"a": rng.integers(0, 3, 500), "b": rng.integers(-5, 700, 500)}
    want = np.lexsort([tab["b"], tab["a"]])
    assert list(stable_order(tab, ["a", "b"])) == list(want)
    done = {k: v[want] for k, v in tab.items()}
    assert stable_order(done, ["a", "b"]) is None
    wide = {"a": tab["a"] * 2 ** 40, "b": tab["b"] * 2 ** 30}
    assert list(stable_order(wide, ["a", "b"])) == list(want)
    mixed = dict(tab, b=tab["b"].astype(np.float32))
    assert list(stable_order(mixed, ["a", "b"])) == list(want)
