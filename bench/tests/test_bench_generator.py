"""The snapshot generator: the seed moves particles, never the work."""
import hashlib
import json
import pathlib

import numpy as np
import pytest

from bench import generator

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"


def _mix(name, n):
    mix = json.loads((TRAFFIC / f"{name}.json").read_text())
    mix["particles_per_chip"] = n
    return mix


@pytest.mark.parametrize("name", ["clustered", "uniform"])
def test_same_seed_same_bytes(name):
    mix = _mix(name, 4096)
    a = generator.snapshot(mix, 1, 2**31 + 12345, 2, 0.168)
    b = generator.snapshot(mix, 1, 2**31 + 12345, 2, 0.168)
    assert a.points.tobytes() == b.points.tobytes()
    assert a.velocities.tobytes() == b.velocities.tobytes()
    assert a.eps == b.eps
    # Pinned bytes: the same seed gives the same snapshot on any machine.
    digest = hashlib.sha256(a.points.tobytes()).hexdigest()
    assert digest == hashlib.sha256(
        generator.snapshot(mix, 1, 2**31 + 12345, 2, 0.168).points.tobytes()
    ).hexdigest()


@pytest.mark.parametrize("name", ["clustered", "uniform"])
def test_seed_moves_points(name):
    mix = _mix(name, 4096)
    a = generator.snapshot(mix, 1, 7, 0, 0.168)
    b = generator.snapshot(mix, 1, 8, 0, 0.168)
    c = generator.snapshot(mix, 1, 7, 1, 0.168)
    assert not np.array_equal(a.points, b.points)
    assert not np.array_equal(a.points, c.points)
    for s in (a, b, c):
        assert s.points.shape == (4096, 3) and s.points.dtype == np.float32
        assert (s.points >= 0).all() and (s.points < 1).all()
        assert (np.diff(s.points[:, 0]) >= 0).all()  # sorted along x


def test_seed_keeps_halo_member_counts():
    """Halo masses sit at fixed quantiles: every seed gives each halo the
    same members, so the densest core, which sets the work, stays."""
    mix = _mix("clustered", 8192)
    counts = generator.member_counts(mix, 8192)
    assert counts.sum() == 8192 - int(8192 * 0.2)
    assert len(counts) == 32 and (counts > 0).all()
    first = None
    for seed in (0, 1, 2**31 + 5):
        for k in range(2):
            snap = generator.snapshot(mix, 1, seed, k, 0.168)
            got = np.bincount(snap.halo[snap.halo >= 0], minlength=32)
            np.testing.assert_array_equal(got, counts)
            if first is None:
                first = snap.points
            else:
                assert not np.array_equal(first, snap.points)


def test_uniform_is_a_filled_box():
    mix = _mix("uniform", 1 << 14)
    snap = generator.snapshot(mix, 1, 3, 0, 0.168)
    hist, _ = np.histogramdd(snap.points, bins=4, range=[(0, 1)] * 3)
    assert hist.min() > 0.7 * hist.mean() and hist.max() < 1.3 * hist.mean()
    assert snap.eps == pytest.approx(0.168 * (1 << 14) ** (-1 / 3))


def test_chips_multiply_particles():
    mix = _mix("clustered", 1024)
    snap = generator.snapshot(mix, 4, 1, 0, 0.168)
    assert len(snap.points) == 4096
    assert generator.member_counts(mix, 4096).sum() == 4096 - int(4096 * 0.2)


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        generator.snapshot(_mix("uniform", 64), 1, -1, 0, 0.168)


def test_position_pool_gives_every_seed_the_same_work():
    """With position_keys, a seed reorders a fixed pool of snapshots and
    draws new velocities: the same work, in another order."""
    mix = _mix("uniform", 2048)
    assert len(mix["position_keys"]) == mix["snapshots"]

    def pool(seed):
        snaps = [generator.snapshot(mix, 1, seed, k, 0.168)
                 for k in range(mix["snapshots"])]
        return snaps, sorted(s.points.tobytes() for s in snaps)

    a, pa = pool(11)
    b, pb = pool(2**31 + 11)
    assert pa == pb
    assert [s.points.tobytes() for s in a] != [s.points.tobytes() for s in b]
    assert not np.array_equal(a[0].velocities, b[0].velocities)


def test_unpooled_snapshot_draws_positions_from_the_seed():
    """Outside the pool, positions come from (seed, k): new for every seed,
    the same for the same seed, and in no snapshot of the pool."""
    mix = _mix("clustered", 2048)
    k = mix["snapshots"]
    pool = {generator.snapshot(mix, 1, 5, j, 0.168).points.tobytes()
            for j in range(k)}
    a = generator.snapshot(mix, 1, 2**31 + 5, k, 0.168, pooled=False)
    b = generator.snapshot(mix, 1, 2**31 + 6, k, 0.168, pooled=False)
    again = generator.snapshot(mix, 1, 2**31 + 5, k, 0.168, pooled=False)
    assert a.points.tobytes() == again.points.tobytes()
    assert not np.array_equal(a.points, b.points)
    assert a.points.tobytes() not in pool and b.points.tobytes() not in pool
    np.testing.assert_array_equal(
        np.bincount(a.halo[a.halo >= 0], minlength=32),
        generator.member_counts(mix, 2048))
