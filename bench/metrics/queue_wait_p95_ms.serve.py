"""95th percentile of the requests' queue waits, in ms: the program's
``request_queued`` events (a request's arrival to its first admission into
a slot; requeues after preemption, marked ``resume``, left out), linear
between order statistics as ``serve_cell.percentile``."""
from bench.serve_cell import percentile


def read(rec):
    waits = [e["dur"] / 1e3 for e in rec["spans"]
             if e["name"] == "request_queued" and not e["args"]["resume"]]
    return percentile(waits, 95) if waits else None
