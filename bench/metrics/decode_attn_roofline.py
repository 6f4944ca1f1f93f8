"""Paged decode kernel (kernels/paged_decode_attention.py): the least
time the occupied slots' attention needs (their real contexts), over
the kernel's device time in the decode programs of the trace, in
percent."""
import _steps


def read(facts):
    ns = _steps.device_ns(facts, "decode", kernel=True)
    if ns is None:
        return None
    w = facts["trace"]["decode_work"]
    if not w["attn_flops"]:
        return None
    return 100.0 * _steps.least_s(facts, w["attn_flops"],
                                  w["attn_bytes"]) / (ns * 1e-9)
