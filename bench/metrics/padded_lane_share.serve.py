"""Share of the prefill-chunk lanes that carried no prompt token: 1 minus
the prompt tokens prefilled (the scheduler's ``prefill_tokens`` counter)
over n_slots x width summed over the scheduler's ``prefill_chunk`` spans.
Every lane of a chunk call is computed, gated or not."""


def read(rec):
    spans = [e for e in rec["spans"] if e["name"] == "prefill_chunk"]
    lanes = rec["model"]["serve"]["n_slots"] * sum(
        e["args"]["width"] for e in spans)
    if not lanes:
        return None
    return 100.0 * (1.0 - rec["counters"]["prefill_tokens"] / lanes)
