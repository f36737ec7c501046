"""``decode_ms_per_token``: the engine's decode loop per decoded position
(one step of the whole batch): generate spans minus prefill spans, over
the decode steps run (served tokens per row minus the one prefill
gives)."""


def read(data):
    """Decode milliseconds per step."""
    gen = data["spans"].get("bench.generate")
    pre = data["spans"].get("bench.prefill")
    steps = data.get("decode_steps", 0)
    if not gen or not pre or steps <= 0:
        return None
    return 1e3 * (sum(gen) - sum(pre)) / steps
