"""Dispatch (executor/runner.py): records of the window that compiled (a
jit cache miss or a compile_ms). Warm-up covers every shape: 0 expected."""

UNIT = "count"


def read(ctx):
    if not ctx.records:
        return None
    return sum(1 for s in ctx.samples
               if (rec := ctx.records.get(s["qid"])) is not None
               and (rec.get("compile_ms") or rec.get("recompiles")
                    or rec.get("jit_cache_hit") is False))
