"""Share of the traced window in which the chip was idle while a host
phase of a step other than ``*_device`` was in progress (lock wait,
build, admission, finish, commit), in percent.  It is a part of
``device_idle_share``."""
import _phases


def read(facts):
    tr = facts["trace"]
    if not tr:
        return None
    host = _phases.host_phase_intervals(facts)
    if not host:
        return None
    d = tr["devices"][0]
    span = (d.window_ns[1] - d.window_ns[0]) * 1e-9
    if span <= 0:
        return None
    idle = _phases.overlap_s(_phases.idle_intervals(facts), host)
    return 100.0 * idle / span
