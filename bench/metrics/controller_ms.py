"""``controller_ms``: host time of the ALERT controller per request — the
harness's span around the server's ``controller.select`` and
``controller.observe`` (the S=1 batched engine pick and the Kalman
feedback)."""


def read(data):
    """Controller milliseconds per request."""
    spans = data["spans"].get("bench.controller")
    if not spans or not data.get("units"):
        return None
    return 1e3 * sum(spans) / data["units"]
