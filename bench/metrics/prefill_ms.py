"""``prefill_ms``: mean time of the engine's prefill at the picked level,
the harness's span around ``ServeEngine.prefill`` ending when its logits
are ready."""


def read(data):
    """Mean prefill milliseconds."""
    spans = data["spans"].get("bench.prefill")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
