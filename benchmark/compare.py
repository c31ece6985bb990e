"""The comparison that decides `correct`: the rows the callbacks received
from the window's own events against the plain reference's rows for the
same events, at the timed sizes, every row of every key and query.

A configuration's `compare` names the columns that line the two sides up
(`align`: both are stably sorted by them, so within equal keys the served
side keeps its delivery order and the reference its arrival order), the
columns that must be equal (`exact`), the float columns with the relative
error each may show, and `ordered`: {"within": [columns], "by": column},
which counts the served rows that were delivered after a row of the same
`within` group with a larger `by` ("per-key order kept").  Every number
compared is returned beside its limit.
"""
import numpy as np
import pandas as pd


def key_ids(values, key_table):
    """Key strings -> their index in key_table; -1 for a stranger."""
    codes, uniques = pd.factorize(np.asarray(values, object))
    index = {str(k): i for i, k in enumerate(key_table)}
    ids = np.fromiter((index.get(str(u), -1) for u in uniques), np.int64,
                      len(uniques))
    return ids[codes] if len(uniques) else np.empty(0, np.int64)


def stable_order(tab, by):
    """A stable sorting order of the table by the `by` columns, or None
    where it is sorted already.  Integer columns are packed into one key
    (16 bits where they fit, which numpy sorts by radix)."""
    n = len(tab[by[0]])
    if n < 2:
        return None
    if all(tab[c].dtype.kind in "iu" for c in by):
        key, total = np.zeros(n, np.int64), 1
        for c in by:
            lo, hi = int(tab[c].min()), int(tab[c].max())
            total *= hi - lo + 1
            if total >= 2 ** 62:
                break
            key = key * (hi - lo + 1) + (tab[c] - lo)
        else:
            if total <= 65536:
                key = key.astype(np.uint16)
            if bool((key[1:] >= key[:-1]).all()):
                return None
            return np.argsort(key, kind="stable")
    return np.lexsort([tab[k] for k in reversed(by)])


def _sorted(tab, by):
    order = stable_order(tab, by)
    return tab if order is None else {k: v[order] for k, v in tab.items()}


def _same(a, b):
    if a.dtype.kind == "f" or b.dtype.kind == "f":
        return a.astype(np.float32) == b.astype(np.float32)
    return a == b


def out_of_order(served, within, by):
    """Rows delivered after a row of their group that has a larger `by`."""
    n = len(served[by])
    if n < 2:
        return 0
    order = stable_order(served, within)
    if order is None:
        order = slice(None)
    same = np.ones(n - 1, bool)
    for k in within:
        col = served[k][order]
        same &= col[1:] == col[:-1]
    val = served[by][order]
    return int((same & (val[1:] < val[:-1])).sum())


def compare_rows(served, reference, spec, key_tables=None):
    """-> {name: {"value", "limit", "op"}}.  `served` holds key strings in
    the columns named by key_tables, `reference` integer ids."""
    served = dict(served)
    for col, tab in (key_tables or {}).items():
        if col in served:
            served[col] = key_ids(served[col], tab)
    cols = sorted(set(spec["align"]) | set(spec["exact"]) |
                  set(spec["float"]))
    missing = [c for c in cols if c not in served or c not in reference]
    if missing:
        raise KeyError(f"columns missing from a side: {missing}")
    out = {}
    if spec.get("ordered"):
        out["rows_out_of_order"] = {
            "value": out_of_order(served, spec["ordered"]["within"],
                                  spec["ordered"]["by"]),
            "limit": 0, "op": "<="}
    s = _sorted({c: np.asarray(served[c]) for c in cols}, spec["align"])
    r = _sorted({c: np.asarray(reference[c]) for c in cols}, spec["align"])
    n_s, n_r = len(s["__ts"]), len(r["__ts"])
    n = min(n_s, n_r)
    ok = np.ones(n, bool)
    for c in spec["exact"]:
        ok &= _same(s[c][:n], r[c][:n])
    out["rows_reference"] = {"value": n_r, "limit": 1, "op": ">="}
    out["rows_unmatched"] = {"value": int(abs(n_s - n_r) + (~ok).sum()),
                             "limit": 0, "op": "<="}
    for c, limit in spec["float"].items():
        a = s[c][:n].astype(np.float64)
        b = r[c][:n].astype(np.float64)
        # a NaN on either side must fail: nanmax would hide it
        err = (np.abs(a - b) / np.maximum(np.abs(b), 1.0))[ok]
        worst = float(err.max()) if len(err) else 0.0
        if len(err) and not np.isfinite(err).all():
            worst = 1e30        # finite, so the result line stays JSON
        out[f"relerr_{c}"] = {"value": worst, "limit": limit, "op": "<="}
    return out


def holds(check):
    v, lim = check["value"], check["limit"]
    return bool(v >= lim) if check["op"] == ">=" else bool(v <= lim)


def verdict(checks):
    return all(holds(c) for c in checks.values())
