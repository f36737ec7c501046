"""``controller_select_ms``: the ALERT controller's pick per request (the
server's ``controller_select`` spans over its ``requests`` counter), from
the program's own spans in the traced part of the window."""

from bench import program_spans


def read(data):
    """Controller-select milliseconds per request."""
    return program_spans.ms_per("serve", "controller_select", "requests",
                                server="alert")
