"""``cache_setup_ms``: mean time of the engine's decode-cache set-up in a
request's first token (its ``cache_setup`` spans, a child of
``first_token``: the decode buffers' shapes and the merge of the
prefill's caches into them), from the program's own spans in the traced
part of the window."""

from bench import program_spans


def read(data):
    """Mean cache set-up milliseconds."""
    return program_spans.mean_ms("engine", "cache_setup")
