"""Model FLOPs of the decode tokens streamed in the traced window (one
per occupied slot and step, attention over its real context) over
(device time of the decode programs x the chip's bf16 peak), in
percent."""
import _steps


def read(facts):
    ns = _steps.device_ns(facts, "decode")
    if ns is None:
        return None
    flops = facts["trace"]["decode_work"]["flops"]
    peak = facts["trace"]["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (ns * 1e-9 * peak) if flops else None
