"""``page_in_ms_per_round``: the host gateway's LRU session paging (its
``page_in`` spans: lane choice, bank export and import) per round
served, from the program's flight recorder in the traced run."""


def read(data):
    """Paging milliseconds per served round."""
    spans = data.get("page_in_s")
    rounds = data.get("serve_round_s")
    if not spans or not rounds:
        return None
    return 1e3 * sum(spans) / len(rounds)
