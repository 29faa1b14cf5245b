"""Share of the traced window in which the device sat idle while the host
ran a serving phase with work in hand: the idle seconds under every host
annotation except ``serve.wait_arrival`` (the engine's sleep when nothing
is queued, prefilling or decoding), ``host: between calls`` included,
over the window, in % (``bench/idle_spans.py``)."""
from bench import idle_spans


def read(rec):
    if rec["kind"] != "serve" or rec["trace"]["window_s"] <= 0:
        return None
    spans = idle_spans.attach(rec)
    if spans is None:
        return None
    stall = sum(v for k, v in spans.items() if k != "serve.wait_arrival")
    return 100.0 * stall / rec["trace"]["window_s"]
