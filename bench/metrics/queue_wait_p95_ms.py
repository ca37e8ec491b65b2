"""queue_wait_p95_ms: 95th percentile of how long a request waited before
its cohort's dispatch began, from its scheduled arrival to the frontend's
``PendingResult.t_dispatch`` stamp (host clock), over the requests sent
in the window that were dispatched. None where the program stamps no
dispatch. The percentile is a rank, as in ``p95_ms``."""
import numpy as np


def read(run):
    wait = [(r.handle.t_dispatch - r.t_sched) * 1e3 for r in run.requests
            if getattr(r.handle, "t_dispatch", None) is not None]
    if not wait:
        return None
    return float(np.percentile(wait, 95, method="inverted_cdf"))
