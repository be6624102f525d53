"""Mean fenced seconds of the halo_catalog program per step of the window."""


def read(run):
    spans = [s["spans"]["halo_catalog"] for s in run["steps"]
             if "halo_catalog" in s["spans"]]
    return sum(spans) / len(spans) if spans else None
