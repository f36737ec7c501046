"""``plan_admit_ms_per_round``: the megatick planner's admission per round
served (its ``plan_admit`` spans: arrival submission, EDF pop, deferral
requeue, fail-fast marking, over its ``rounds`` counter), from the
program's own spans in the traced part of the window."""

from bench import program_spans


def read(data):
    """Admission milliseconds per round."""
    return program_spans.ms_per("megatick", "plan_admit", "rounds",
                                gateway="megatick")
