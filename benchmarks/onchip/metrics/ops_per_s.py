"""ops_per_s: operations (reads and inserts) completed in the window,
divided by the window."""


def read(ctx):
    return ctx.completed_in_window() / ctx.seconds
