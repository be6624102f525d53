"""HBM roofline share of one library eps min-label pass, run after the
window: the least bytes the pass must move (bench/roofline.py, neighbor
total from the plain reference) at the chip's peak bandwidth, over the
pass's device time, the busy time of the device in a profiler trace that
holds that pass alone."""
from bench import roofline


def read(run):
    probe = run["probe"]
    if not probe.get("eps_pass_device_s") or "neighbor_total" not in probe:
        return None
    nbytes = roofline.eps_pass_bytes(probe["n"], probe["neighbor_total"])
    bw = roofline.peak(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes / bw) / probe["eps_pass_device_s"]
