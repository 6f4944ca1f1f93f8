"""Mean wall time of one prefill chunk on the prefill worker: the
runtime's ``prefill_chunk`` spans that end in the window."""


def read(facts):
    d = [s["dur"] for s in facts["spans"] if s["name"] == "prefill_chunk"]
    return 1e3 * sum(d) / len(d) if d else None
