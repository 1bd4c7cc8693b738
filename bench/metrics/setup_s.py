"""Set-up: process start to the window's opening (table, ingest, program,
warm-up)."""

UNIT = "s"


def read(r):
    return r.setup_s
