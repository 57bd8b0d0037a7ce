"""What the readers of the wide-key metrics share: the window's queries
whose sparse group key rode the sort as more than one int64 word, which are
those whose history record says `key_words` >= 2 (a group space of 2^62 or
more). A program without the counter (an older commit) gives nothing to
read: every function here then returns None or nothing, and never raises."""


def _wide(rec) -> bool:
    return rec is not None and (rec.get("key_words") or 0) >= 2


def served(ctx):
    """(sample, record) of the window's requests with a wide key."""
    for s in ctx.samples:
        rec = ctx.records.get(s["qid"])
        if _wide(rec):
            yield s, rec


def traced(ctx):
    """(trace query, record) of the traced window's whole queries with a
    wide key."""
    if ctx.trace is None:
        return
    for q in ctx.trace["queries"]:
        rec = ctx.records.get(q["qid"])
        if q["whole"] and _wide(rec):
            yield q, rec
