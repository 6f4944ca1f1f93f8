"""Mean host time a decode step spends packing its slot inputs (block
tables, positions, copy-on-write replay) before the jitted call: the
runtime's ``decode_build`` spans that end in the window."""


def read(facts):
    d = [s["dur"] for s in facts["spans"] if s["name"] == "decode_build"]
    return 1e3 * sum(d) / len(d) if d else None
