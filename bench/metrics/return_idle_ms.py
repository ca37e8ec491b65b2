"""return_idle_ms: device idle time under the frontend's ``frontend.sync``
and ``frontend.translate`` spans (fetching scores and slots to the host,
translating slots to page ids), per dispatch in the window, in ms. Billed
as ``launch_idle_ms``; None where the program writes no
``frontend.flush`` span."""
from bench import program_spans as PS


def read(run):
    sp = PS.read(run)
    if sp is None or not sp.flushes:
        return None
    return PS.per_dispatch_ms(run, sp.idle_s.get(PS.SYNC, 0.0)
                              + sp.idle_s.get(PS.TRANSLATE, 0.0))
