"""Shared by the phase readers: the runtime's step-phase spans
(``prefill_lock`` ... ``decode_commit``, docs/observability.md) in the
window, and where they lie against the device trace.  A run whose
program emits no phase spans gives these readers nothing to read."""
import flops as FL
import xplane as XP

#: host phases of a step, the ``*_device`` phases left out
HOST_PHASES = ("prefill_lock", "prefill_build", "prefill_finish",
               "decode_lock", "decode_admit", "decode_build",
               "decode_commit")


def spans(facts, name):
    return [s for s in facts["spans"] if s["name"] == name]


def inside(s, ta, tb):
    """Seconds of span ``s`` inside [ta, tb]."""
    return max(0.0, min(s["ts"] + s["dur"], tb) - max(s["ts"], ta))


def share_inside(s, ta, tb):
    """Share of span ``s`` inside [ta, tb]; a span of no length counts
    whole when it starts there."""
    if s["dur"] <= 0:
        return 1.0 if ta <= s["ts"] <= tb else 0.0
    return inside(s, ta, tb) / s["dur"]


def segment_flops(dims, start, length, ends_prompt):
    """Model FLOPs of one prefill segment: ``length`` prompt tokens from
    ``start`` through every layer, their causal attention over keys
    ``start + 1`` to ``start + length``, and the head once if the
    segment ends its prompt."""
    return (length * FL.matmul_flops_per_token(dims)
            + FL.prefill_attn_flops(dims, start + length)
            - FL.prefill_attn_flops(dims, start)
            + (FL.head_flops(dims) if ends_prompt else 0))


def overlap_s(a, b):
    """Seconds in both of two lists of disjoint (start, end) intervals,
    each sorted by start."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(facts):
    """The device's idle intervals in the trace, on the spans' clock."""
    tr = facts["trace"]
    d = tr["devices"][0]
    off = tr["span_s"][0] - d.window_ns[0] * 1e-9
    return sorted((s * 1e-9 + off, (s + n) * 1e-9 + off)
                  for s, n in d.gaps)


def host_phase_intervals(facts):
    return XP.union([(s["ts"], s["ts"] + s["dur"]) for s in facts["spans"]
                     if s["name"] in HOST_PHASES])
