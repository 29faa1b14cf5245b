"""Share of the paged verify kernel's page blocks that it computes, in %:
the scheduler's ``verify_page_blocks_live`` counter over its
``verify_page_blocks_total``, summed over the window's chunk calls.  A
block past every lane of its lane block's causal frontier is skipped, so
the share says how much of the chunk's attention grid the skip removes.
None where the program has no such counters."""


def read(rec):
    total = rec["counters"].get("verify_page_blocks_total")
    if not total:
        return None
    return 100.0 * rec["counters"]["verify_page_blocks_live"] / total
