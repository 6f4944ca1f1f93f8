"""Share of the prefill program's token slots that held padding, in
percent: 1 - (segment tokens) / (rows x cols of the padded batch), over
the ``prefill_device`` spans that end in the window."""
import _phases


def read(facts):
    dev = _phases.spans(facts, "prefill_device")
    slots = sum(s["args"]["rows"] * s["args"]["cols"] for s in dev)
    used = sum(n for s in dev for _, _, n in s["args"]["segs"])
    return 100.0 * (1.0 - used / slots) if slots else None
