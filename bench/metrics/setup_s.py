"""``setup_s``: seconds from the command's start to the measured window
(imports, weights, traffic, compiles or cache loads, warm-up)."""


def read(data):
    """Set-up seconds, as the harness timed them."""
    return data["setup_s"]
