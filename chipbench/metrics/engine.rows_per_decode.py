"""Live rows a decode program carried, averaged over the window's decode
calls: the recorder notes one position per live row of each call."""


def read(run):
    rows = [len(c["positions"]) for c in run.calls if c["kind"] == "decode"]
    return sum(rows) / len(rows) if rows else None
