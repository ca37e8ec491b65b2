"""qps: queries answered in the window, divided by the window's length
(host clock). A query counts when its answer came back inside the
window; one still in flight at the close does not."""


def read(run):
    done = sum(1 for r in run.requests
               if r.handle.done() and r.handle.error is None
               and run.t0 <= r.handle.t_done <= run.t1)
    return done / (run.t1 - run.t0) if done else None
