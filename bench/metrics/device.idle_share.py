"""1 - busy / window over one traced step, the mean over the cell's
devices, in percent: busy is the union of the device's op intervals in
the profiler trace."""


def read(run):
    red = run["trace"]
    if not red or not red["devices"]:
        return None
    shares = [1.0 - d["busy_s"] / red["window_s"] for d in red["devices"]]
    return 100.0 * sum(shares) / len(shares)
