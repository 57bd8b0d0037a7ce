"""What the readers of the scatter and sparse metrics share: the window's
queries by the `reduce_path` of their history record (`pallas`, `scatter`,
`sparse`, or `reduce` for an ungrouped masked reduce). A program without the counter (an older commit) gives nothing to read:
every function here then returns None or nothing, and never raises."""


def served_by(ctx, path: str):
    """(sample, record) of the window's requests that `path` served."""
    for s in ctx.samples:
        rec = ctx.records.get(s["qid"])
        if rec is not None and rec.get("reduce_path") == path:
            yield s, rec


def _traced(ctx, path: str):
    """(trace query, record) of the traced window's whole queries that
    `path` served."""
    if ctx.trace is None:
        return
    for q in ctx.trace["queries"]:
        rec = ctx.records.get(q["qid"])
        if q["whole"] and rec is not None \
                and rec.get("reduce_path") == path:
            yield q, rec


def ms_per_query(ctx, path: str):
    busy = [q["device_s"] for q, _rec in _traced(ctx, path)]
    return 1000.0 * sum(busy) / len(busy) if busy else None


def roofline(ctx, path: str):
    need, busy = 0, 0.0
    for q, rec in _traced(ctx, path):
        need += ctx.dataset.needed_bytes(q["template"], ctx.reference,
                                         rec.get("rows_scanned"))
        busy += q["device_s"]
    if busy <= 0:
        return None
    least_s = need / (ctx.peaks["hbm_bytes_per_s"] * ctx.chips)
    return 100.0 * least_s / busy
