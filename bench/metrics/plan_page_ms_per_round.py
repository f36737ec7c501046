"""``plan_page_ms_per_round``: the megatick planner's LRU paging
bookkeeping per round served (its ``plan_page`` spans over its
``rounds`` counter), from the program's own spans in the traced part of
the window."""

from bench import program_spans


def read(data):
    """Paging-bookkeeping milliseconds per round."""
    return program_spans.ms_per("megatick", "plan_page", "rounds",
                                gateway="megatick")
