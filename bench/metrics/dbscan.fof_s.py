"""Mean fenced seconds of the fdbscan program per step of the window."""


def read(run):
    spans = [s["spans"]["fdbscan"] for s in run["steps"]
             if "fdbscan" in s["spans"]]
    return sum(spans) / len(spans) if spans else None
