"""Paged prefill kernel (kernels/paged_prefill_attention.py): the least
time its unpadded work needs, the larger of FLOPs / peak and bytes /
bandwidth (bench/flops.py), over the kernel's device time in the
prefill programs of the trace, in percent."""
import _steps


def read(facts):
    ns = _steps.device_ns(facts, "prefill", kernel=True)
    if ns is None:
        return None
    w = facts["trace"]["prefill_work"]
    if not w["attn_flops"]:
        return None
    return 100.0 * _steps.least_s(facts, w["attn_flops"],
                                  w["attn_bytes"]) / (ns * 1e-9)
