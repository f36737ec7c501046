"""``first_token_ms``: mean time from entering the engine's generate to
the first token on the host (cache set-up, prefill, argmax, fetch; its
``first_token`` spans), from the program's own spans in the traced part
of the window."""

from bench import program_spans


def read(data):
    """Mean first-token milliseconds."""
    return program_spans.mean_ms("engine", "first_token")
