"""``decided_per_s``: offered requests given a disposition (served,
failed fast, refused at a full queue) per second, over whole horizons and
all of the window's time."""


def read(data):
    """Decided requests over the window's seconds."""
    if "decided" not in data or data["elapsed_s"] <= 0:
        return None
    return data["decided"] / data["elapsed_s"]
