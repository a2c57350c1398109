"""Process start to the first request of the window: native build, boot,
restore, staging, warm-up and, in a run that compiles, compilation."""


def read(ctx):
    return ctx.setup_s
