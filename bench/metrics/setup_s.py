"""setup_s: seconds from process start to the start of the window (host
clock): start-up, corpus generation and ingest, compiles or compile-cache
loads, and warming the cell's buckets."""


def read(run):
    return run.setup_s
