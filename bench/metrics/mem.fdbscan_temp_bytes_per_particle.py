"""The compiled fdbscan program's temporaries (memory_analysis) over the
particles it holds."""


def read(run):
    temp = run["probe"].get("fdbscan_temp_bytes")
    return None if temp is None else temp / run["n_per_device"]
