"""Time a decode step's device phase spent other than running its own
program, per step, in ms: (the ``decode_device`` spans' time inside the
trace - device time of the decode programs in the trace) / the decode
steps in the trace, each counted by the share of its ``decode_device``
span inside it.  That is the wait behind prefill programs on the shared
chip, plus dispatch, upload and readback."""
import _phases
import _steps


def read(facts):
    ns = _steps.device_ns(facts, "decode")
    if ns is None:
        return None
    ta, tb = facts["trace"]["span_s"]
    dev = _phases.spans(facts, "decode_device")
    steps = sum(_phases.share_inside(s, ta, tb) for s in dev)
    if steps <= 0:
        return None
    host = sum(_phases.inside(s, ta, tb) for s in dev)
    return 1e3 * (host - ns * 1e-9) / steps
