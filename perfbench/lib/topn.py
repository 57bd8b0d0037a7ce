"""What the readers of the TopN metrics share: the window's queries that
the program planned as a TopN, which are those whose history record carries
the counter `topn_group_space` (the dense group space K of the TopN's
dimension), whatever path served them. A program without the counter (an
older commit) gives nothing to read: every function here then returns None
or nothing, and never raises."""


def served(ctx):
    """(sample, record) of the window's TopN requests."""
    for s in ctx.samples:
        rec = ctx.records.get(s["qid"])
        if rec is not None and rec.get("topn_group_space") is not None:
            yield s, rec


def traced(ctx):
    """(trace query, record) of the traced window's whole TopN queries."""
    if ctx.trace is None:
        return
    for q in ctx.trace["queries"]:
        rec = ctx.records.get(q["qid"])
        if q["whole"] and rec is not None \
                and rec.get("topn_group_space") is not None:
            yield q, rec
