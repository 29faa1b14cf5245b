"""95th percentile of the requests' prefill times, in ms: the program's
``request_prefill`` events (a request's admission into a slot to the host
holding its first token, across every chunk call and the decode bursts
interleaved with them; requeues, marked ``resume``, left out), linear
between order statistics as ``serve_cell.percentile``."""
from bench.serve_cell import percentile


def read(rec):
    times = [e["dur"] / 1e3 for e in rec["spans"]
             if e["name"] == "request_prefill" and not e["args"]["resume"]]
    return percentile(times, 95) if times else None
