"""Queries answered by the window's close, over the window's seconds."""

UNIT = "queries/s"


def read(r):
    return r.answered_by_close / r.seconds
