"""Model FLOPs of the prefill segments the traced chunks ran (the
``segs`` of each ``prefill_device`` span, weighted by the share of the
span inside the trace) over (device time of the prefill programs in the
trace x the chip's bf16 peak), in percent.  A segment ends its prompt
when no segment of the same request starts where it ends."""
import _phases
import _steps


def read(facts):
    ns = _steps.device_ns(facts, "prefill")
    if ns is None:
        return None
    ta, tb = facts["trace"]["span_s"]
    dev = _phases.spans(facts, "prefill_device")
    starts = {(rid, a) for s in dev for rid, a, _ in s["args"]["segs"]}
    flops = 0.0
    for s in dev:
        f = _phases.share_inside(s, ta, tb)
        if f > 0:
            flops += f * sum(
                _phases.segment_flops(facts["dims"], a, n,
                                      (rid, a + n) not in starts)
                for rid, a, n in s["args"]["segs"])
    peak = facts["trace"]["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (ns * 1e-9 * peak) if flops else None
