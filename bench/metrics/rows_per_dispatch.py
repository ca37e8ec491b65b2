"""rows_per_dispatch: query rows per cascade dispatch over the window, from
the frontend's own counters (``stats['rows_real']`` and
``stats['dispatches']``). Layer: the frontend's micro-batching."""


def read(run):
    n = run.counters["dispatches"]
    return run.counters["rows_real"] / n if n else None
