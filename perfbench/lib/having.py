"""What the readers of the HAVING metrics share: the window's queries whose
GroupBy carried a HAVING, which are those whose history record carries the
counter `having_groups_in` (the present groups the predicate was tested
on), whoever decided it (the record's `having_where`: `device` | `host`).
A program without the counter (an older commit) gives nothing to read:
every function here then returns None or nothing, and never raises."""


def served(ctx):
    """(sample, record) of the window's requests with a HAVING."""
    for s in ctx.samples:
        rec = ctx.records.get(s["qid"])
        if rec is not None and rec.get("having_groups_in") is not None:
            yield s, rec


def traced(ctx):
    """(trace query, record) of the traced window's whole queries with a
    HAVING."""
    if ctx.trace is None:
        return
    for q in ctx.trace["queries"]:
        rec = ctx.records.get(q["qid"])
        if q["whole"] and rec is not None \
                and rec.get("having_groups_in") is not None:
            yield q, rec
