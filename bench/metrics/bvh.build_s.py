"""Mean fenced seconds of scene bounds + build_bvh on snapshot 0, after
the window."""


def read(run):
    return run["probe"].get("bvh_build_s")
