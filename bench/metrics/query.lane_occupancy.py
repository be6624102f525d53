"""Sum of nodes visited over n x the longest lane's, in one eps
within-pass with the traversal counters on: the share of lockstep lane
iterations that do work."""


def read(run):
    return run["probe"].get("lane_occupancy")
