"""``select_ms_per_round``: the host gateway's per-round select (its
``select`` spans: the masked engine call and the fetch of its picks,
over its ``rounds`` counter), from the program's own spans in the traced
part of the window."""

from bench import program_spans


def read(data):
    """Select milliseconds per served round."""
    return program_spans.ms_per("gateway", "select", "rounds",
                                gateway="host")
