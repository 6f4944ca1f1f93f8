"""Share of the traced window in which no operation ran on the chip,
in percent: 1 - (union of the device op intervals) / (trace span)."""


def read(facts):
    tr = facts["trace"]
    if not tr:
        return None
    d = tr["devices"][0]
    span = d.window_ns[1] - d.window_ns[0]
    return 100.0 * (1.0 - d.busy_ns / span) if span > 0 else None
