"""gen_lag_p95_ms: 95th percentile of how late each ``submit`` ran behind
its scheduled arrival (host clock), for an open-loop mix. A late generator
is a host that cannot keep the schedule, not a fast server."""
import numpy as np


def read(run):
    if run.cell.traffic["mode"] != "open" or not run.requests:
        return None
    lag = [(r.t_sent - r.t_sched) * 1e3 for r in run.requests]
    return float(np.percentile(lag, 95))
