"""The plain reference, the checks built on it, and the roofline counts."""
import numpy as np
import pytest

from bench import reference as ref
from bench import roofline


def _line(xs, y=0.5, z=0.5):
    return np.array([[x, y, z] for x in xs], np.float32)


def test_fof_links_within_eps_and_labels_by_smallest_index():
    # chain 0-1-2 at spacing 0.1, a lone point, a pair 4-5
    pts = _line([0.1, 0.2, 0.3, 0.7, 0.9, 0.95])
    f = ref.fof(pts, 0.1001)
    np.testing.assert_array_equal(f.labels, [0, 0, 0, -1, 4, 4])
    assert ref.neighbor_total(f, len(pts)) == 6 + 2 * 3
    assert ref.check_fof(f.labels, f) == 0


def test_check_fof_counts_wrong_particles():
    pts = _line([0.1, 0.2, 0.3, 0.7, 0.9, 0.95])
    f = ref.fof(pts, 0.1001)
    assert ref.check_fof(np.array([0, 0, 0, -1, 4, 4]), f) == 0
    # one particle of the chain split off
    assert ref.check_fof(np.array([0, 0, 2, -1, 4, 4]), f) >= 1
    # the lone point given a label
    assert ref.check_fof(np.array([0, 0, 0, 3, 4, 4]), f) >= 1
    # two clusters joined that no link joins
    assert ref.check_fof(np.array([0, 0, 0, -1, 0, 0]), f) >= 2
    # labelled by a larger index than the smallest member's
    assert ref.check_fof(np.array([1, 1, 1, -1, 4, 4]), f) == 3
    # half the particles left out (noise)
    assert ref.check_fof(np.array([0, 0, 0, -1, -1, -1]), f) == 2


def test_ambiguous_pair_may_go_either_way():
    eps = 0.1
    pts = _line([0.2, 0.2 + eps])          # at the limit, to float32
    f = ref.fof(pts, eps)
    assert ref.check_fof(np.array([0, 0]), f) == 0
    assert ref.check_fof(np.array([-1, -1]), f) == 0


def test_catalog_by_hand():
    pts = _line([0.1, 0.2, 0.3, 0.7, 0.9, 0.95])
    vel = np.array([[1, 0, 0], [-1, 0, 0], [0, 0, 0],
                    [5, 5, 5], [0, 2, 0], [0, 0, 0]], np.float32)
    cat = ref.catalog(pts, vel, np.array([0, 0, 0, -1, 4, 4]), 2)
    np.testing.assert_array_equal(cat.root, [0, 4])
    np.testing.assert_array_equal(cat.count, [3, 2])
    np.testing.assert_allclose(cat.center[:, 0], [0.2, 0.925], rtol=1e-6)
    np.testing.assert_allclose(cat.vmean[1], [0, 1, 0])
    np.testing.assert_allclose(cat.vdisp, [np.sqrt(2 / 3), 1.0])
    np.testing.assert_allclose(cat.rmax, [0.1, 0.025], rtol=1e-5)
    np.testing.assert_array_equal(cat.slot, [0, 0, 0, -1, 1, 1])
    got = {"num_halos": 2, "overflow": False, "root": np.array([0, 4, -1]),
           "count": np.array([3, 2, 0]), "particle_halo": cat.slot,
           "center": cat.center.astype(np.float32),
           "rmax": cat.rmax, "vmean": cat.vmean, "vdisp": cat.vdisp,
           "mass": cat.count.astype(np.float32)}
    assert ref.check_catalog_counts(got, cat) == 0
    assert ref.check_catalog_values(got, cat, 0.1) < 1e-5
    got["count"] = np.array([3, 3, 0])
    assert ref.check_catalog_counts(got, cat) == 1


def test_most_bound_gap_and_so_fixed_point():
    rng = np.random.default_rng(0)
    pts = np.concatenate([0.5 + 0.01 * rng.standard_normal((200, 3)),
                          rng.uniform(0, 1, (2000, 3))]).astype(np.float32)
    eps = 0.02
    f = ref.fof(pts, eps)
    cat = ref.catalog(pts, np.zeros_like(pts), f.labels, 2)
    phi = ref.potentials(pts, f, eps, eps * 1e-2)
    h = len(cat.root)
    best = np.array([np.argmin(np.where(cat.slot == s, phi, np.inf))
                     for s in range(h)])
    assert ref.check_centers(best, pts[best], pts, cat, phi) == 0.0
    worse = best.copy()
    worse[0] = np.argmax(np.where(cat.slot == 0, phi, -np.inf))
    assert ref.check_centers(worse, pts[worse], pts, cat, phi) > 0.1
    outsider = best.copy()
    outsider[0] = best[1]
    assert ref.check_centers(outsider, pts[outsider], pts, cat,
                             phi) == np.inf
    best = int(best[0])
    r, cnt, br = ref.so_bisect(pts, pts[[best]], delta=200.0, r_max=0.1,
                               iters=10)
    assert cnt[0] > 10 and br[0]
    assert ref.check_so(pts[[best]], r, cnt, cnt.astype(float), br, pts,
                        delta=200.0, r_max=0.1, iters=10) == 0
    assert ref.check_so(pts[[best]], r, cnt + 1, cnt + 1.0, br, pts,
                        delta=200.0, r_max=0.1, iters=10) == 1
    assert ref.check_so(pts[[best]], r / 2, cnt, cnt.astype(float), br, pts,
                        delta=200.0, r_max=0.1, iters=10) == 1


def test_eps_pass_bytes_by_hand():
    # 4 queries, each with itself and one neighbor: 8 (query, neighbor)
    # pairs; 4 x (12 + 4) + 8 x (12 + 4 + 1) = 64 + 136
    assert roofline.eps_pass_bytes(4, 8) == 200
    assert roofline.eps_pass_bytes(0, 0) == 0


def test_peaks_by_device_kind():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("cpu")


def test_eps_pass_roofline_reads_device_time():
    """The share is the least bytes at the peak bandwidth over the pass's
    device time from its trace; with no device time it reads nothing."""
    import importlib.util
    import pathlib

    path = (pathlib.Path(roofline.__file__).with_name("metrics")
            / "query.eps_pass_roofline.py")
    spec = importlib.util.spec_from_file_location("eps_roofline", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    run = {"device_kind": "TPU v5 lite",
           "probe": {"n": 4, "neighbor_total": 8,
                     "eps_pass_device_s": 1e-6}}
    # 200 B at 819 GB/s take 200 / 819e9 s; over 1 us of device time.
    assert reader.read(run) == pytest.approx(100 * 200 / 819e9 / 1e-6)
    run["probe"]["eps_pass_device_s"] = None
    assert reader.read(run) is None
    del run["probe"]["eps_pass_device_s"]
    assert reader.read(run) is None
