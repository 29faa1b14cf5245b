"""Device time per run of the prefill-chunk executable (``jit_chunk``,
``serve/engine.py`` ``build_prefill_chunk``), from the trace."""


def read(rec):
    m = rec["trace"]["modules"].get("jit_chunk")
    if not m or not m["calls"]:
        return None
    return 1e3 * m["device_s"] / m["calls"]
