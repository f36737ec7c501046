"""``plan_ms_per_round``: the megatick host planner's wall time (its
``megatick_plan`` phase timer) per round served."""


def read(data):
    """Planner milliseconds per round."""
    if "plan_s" not in data or not data.get("rounds"):
        return None
    return 1e3 * data["plan_s"] / data["rounds"]
