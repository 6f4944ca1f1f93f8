"""Mean wall time of one decode iteration (admission excluded) on the
decode worker: the runtime's ``decode_step`` spans that end in the
window."""


def read(facts):
    d = [s["dur"] for s in facts["spans"] if s["name"] == "decode_step"]
    return 1e3 * sum(d) / len(d) if d else None
