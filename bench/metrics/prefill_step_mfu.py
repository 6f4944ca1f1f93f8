"""Model FLOPs of the useful (unpadded) prompt tokens prefilled in the
traced window over (device time of the prefill programs x the chip's
bf16 peak), in percent.  A request's work counts in the share of its
prefill interval that lies in the trace."""
import _steps


def read(facts):
    ns = _steps.device_ns(facts, "prefill")
    if ns is None:
        return None
    flops = facts["trace"]["prefill_work"]["flops"]
    peak = facts["trace"]["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (ns * 1e-9 * peak) if flops else None
