"""Set-up: generate, ingest, placement and warm-up (compiles in a run that
compiles). The plain reference's time is not in it."""

UNIT = "s"


def read(ctx):
    return ctx.setup_s
