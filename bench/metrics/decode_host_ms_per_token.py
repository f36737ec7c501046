"""``decode_host_ms_per_token``: the host's share of a decode step — each
``decode_step`` span less its child ``token_fetch`` (the wait for the
token and its copy), over the engine's ``decode_steps`` counter — from
the program's own spans in the traced part of the window."""

from bench import program_spans


def read(data):
    """Host milliseconds per decode step."""
    return program_spans.ms_per("engine", "decode_step", "decode_steps",
                                child="token_fetch")
