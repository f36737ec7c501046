"""``goodput_per_s``: requests whose tokens were all delivered within
their deadline, by the harness's own clock around each request, per
second of the whole window."""


def read(data):
    """Good requests over the window's seconds."""
    if "good" not in data or data["elapsed_s"] <= 0:
        return None
    return data["good"] / data["elapsed_s"]
