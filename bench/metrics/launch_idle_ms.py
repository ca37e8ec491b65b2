"""launch_idle_ms: device idle time under the frontend's ``frontend.pad``
and ``frontend.launch`` spans (padding the cohort, copying it to the
device and enqueueing the cascade), per dispatch in the window, in ms.
Each device gap is billed to the innermost benchmark or frontend span
covering it (``bench.program_spans``). None where the program writes no
``frontend.flush`` span."""
from bench import program_spans as PS


def read(run):
    sp = PS.read(run)
    if sp is None or not sp.flushes:
        return None
    idle = sp.idle_s
    pump = {n: idle.get(n, 0.0) for n in (PS.PAD, PS.LAUNCH, PS.SYNC,
                                          PS.TRANSLATE, PS.FLUSH,
                                          "bench.pump")}
    run.note("launch_idle_ms: device idle under bench.pump "
             f"{sum(pump.values()):.6f}s: "
             + ", ".join(f"{n} {s:.6f}s" for n, s in pump.items())
             + f" (the last outside any flush); {sp.flushes} flush spans")
    return PS.per_dispatch_ms(run, idle.get(PS.PAD, 0.0)
                              + idle.get(PS.LAUNCH, 0.0))
