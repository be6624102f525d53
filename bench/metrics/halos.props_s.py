"""Mean fenced seconds of most_bound_centers + so_masses per step of the
window."""


def read(run):
    spans = [s["spans"]["halo_props"] for s in run["steps"]
             if "halo_props" in s["spans"]]
    return sum(spans) / len(spans) if spans else None
