"""``admit_ms_per_round``: the host gateway's admission per round served
(its ``admit`` spans: arrival submission, EDF pop, deferral, fail-fast,
outside ``serve_round``; over its ``rounds`` counter), from the
program's own spans in the traced part of the window."""

from bench import program_spans


def read(data):
    """Admission milliseconds per served round."""
    return program_spans.ms_per("gateway", "admit", "rounds",
                                gateway="host")
