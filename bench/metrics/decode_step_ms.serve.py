"""Device time per decode step of the burst executable (``jit_burst``,
``serve/scheduler.py`` ``build_burst``): its device seconds over its runs
times the steps of one burst, from the trace."""


def read(rec):
    m = rec["trace"]["modules"].get("jit_burst")
    if not m or not m["calls"]:
        return None
    steps = m["calls"] * rec["model"]["serve"]["decode_burst"]
    return 1e3 * m["device_s"] / steps
