"""The comparison that decides `correct`: equality with the plain reference.

A served answer is the JSON body of `POST /sql`: {"columns": [...], "rows":
[{column: value}, ...]}. It is compared with the dataset's reference answer
by equality, never by closeness: the same columns in the same order, the
same multiset of rows, and the ORDER BY keys in the same sequence (rows that
tie on every ORDER BY key may come in any order, as in SQL).
"""

from __future__ import annotations


def _row_key(row: dict, columns: list) -> tuple:
    return tuple((c, row.get(c)) for c in columns)


def answer_mismatches(served: dict, expected: dict) -> list:
    """Why a served answer differs from the expected one: [] when equal."""
    why = []
    cols = expected["columns"]
    if list(served.get("columns", [])) != list(cols):
        return [f"columns {served.get('columns')} != {cols}"]
    got, exp = served.get("rows", []), expected["rows"]
    if len(got) != len(exp):
        why.append(f"{len(got)} rows != {len(exp)}")
    for row in got:
        for c, v in row.items():
            if isinstance(v, float):
                why.append(f"column {c}: a float {v!r} where the reference "
                           "has an integer")
                return why
    a = sorted(map(repr, (_row_key(r, cols) for r in got)))
    b = sorted(map(repr, (_row_key(r, cols) for r in exp)))
    if a != b:
        diff = sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))
        first = next(((x, y) for x, y in zip(a, b) if x != y), None)
        why.append(f"{diff} rows differ; first: served {first[0]} != "
                   f"reference {first[1]}" if first else
                   f"{diff} rows missing or extra")
    order = [c for c, _d in expected.get("order", [])]
    if order and not why:
        seq_got = [tuple(r[c] for c in order) for r in got]
        seq_exp = [tuple(r[c] for c in order) for r in exp]
        if seq_got != seq_exp:
            i = next(i for i, (x, y) in enumerate(zip(seq_got, seq_exp))
                     if x != y)
            why.append(f"ORDER BY {order}: row {i} is {seq_got[i]}, the "
                       f"reference has {seq_exp[i]}")
    return why
