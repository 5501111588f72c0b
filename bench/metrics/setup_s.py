"""Set-up seconds: from the start of the process to the window's start —
weights, engine, dry run, bucket warm-up (compiles or cache loads) and
the lead-in of live traffic."""


def read(ctx):
    return ctx.setup_s
