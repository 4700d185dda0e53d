"""Rows given a verdict in the window over the window's seconds: the
front end's ``verdicts`` counter, read at both ends of the window."""


def read(ctx):
    return ctx["delta"]["verdicts"] / ctx["window_s"]
