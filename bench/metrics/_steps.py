"""Shared by the step and kernel readers: device time of the prefill and
decode programs, and of the Mosaic kernel inside each, in the trace."""

PROGRAMS = {"prefill": "prefill_paged", "decode": "decode_paged"}


def device_ns(facts, step, kernel=False):
    tr = facts["trace"]
    if not tr or tr["peaks"] is None:
        return None
    d = tr["devices"][0]
    table = d.kernels if kernel else d.modules
    ns = sum(v for k, v in table.items() if PROGRAMS[step] in k)
    return ns or None


def least_s(facts, flops, nbytes):
    p = facts["trace"]["peaks"]
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
