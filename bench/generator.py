"""The one snapshot generator: particle snapshots from a traffic mix's data.

A traffic mix is a JSON file under ``bench/traffic/``; this module reads it
and makes the snapshots. Nothing here imports the program under test.

The stand-in for a HACC snapshot is NFW-like halos in a uniform background
in the unit box ``[0, 1)^3``:

* ``halos`` halos whose member counts are fixed by the mix, not drawn: the
  clustered share of the particles is split by the quantiles
  ``(i + 0.5) / halos`` of a Pareto(``pareto_alpha``) mass function, with
  largest-remainder rounding;
* halo ``i`` has the fixed radius factor ``radius_base + frac((i + 1) *
  radius_step)``, and members at radius ``halo_scale * factor * u^power``
  (``u`` uniform) in a uniform direction, floored at ``r_floor``;
* ``background_share`` of the particles lie uniformly in the box (a mix
  with ``background_share`` 1 and no halos is a filled uniform box);
* with ``sort_axis`` set, particles are ordered along that axis, as a slab
  decomposition leaves them in memory.

Only the halo centers, the radii and directions of members, the background
and the velocities are drawn from ``(seed, snapshot)``. So the amount of
work, which the densest cores set, does not swing from seed to seed. Every
draw uses numpy's PCG64 from the key ``(seed, snapshot, stream)``, so a
seed gives the same bytes on any machine.

A mix with ``position_keys`` (one per snapshot) takes its positions from a
fixed pool instead: snapshot k of a run holds the positions drawn from the
key ``(position_keys[order[k]], 0)``, where ``order`` is a permutation of
the pool drawn from the seed; velocities still come from ``(seed, k)``.
Every seed then does the same work, in another order. It is for mixes
whose work the seed would otherwise move more than the window can average
(a uniform box, whose longest traversal lane has a heavy tail). Such a mix
still checks positions drawn from the seed: ``snapshot(..., pooled=False)``
leaves the pool aside, and the harness runs the timed step on one such
snapshot after the window.
"""
from __future__ import annotations

import json
import pathlib
from typing import NamedTuple

import numpy as np

__all__ = ["Snapshot", "load_mix", "member_counts", "particles", "snapshot"]

# Streams of one snapshot's key (seed, snapshot, stream); _ORDER keys the
# permutation of a position pool, (seed, _ORDER).
_CENTERS, _MEMBERS, _BACKGROUND, _VELOCITIES, _ORDER = range(5)


class Snapshot(NamedTuple):
    points: np.ndarray      # (n, 3) float32 in [0, 1)
    velocities: np.ndarray  # (n, 3) float32
    eps: float              # linking length b (V / n)^(1/3)
    halo: np.ndarray        # (n,) int32 halo each particle was drawn in, -1


def load_mix(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def particles(mix: dict, chips: int) -> int:
    return int(mix["particles_per_chip"]) * int(chips)


def member_counts(mix: dict, n: int) -> np.ndarray:
    """Members of each halo: fixed by the mix and n, never by the seed."""
    halos = int(mix.get("halos", 0))
    n_clustered = n - int(n * float(mix["background_share"]))
    if halos == 0:
        return np.zeros((0,), np.int64)
    q = (np.arange(halos) + 0.5) / halos
    w = (1.0 - q) ** (-1.0 / float(mix["pareto_alpha"]))
    share = n_clustered * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    rest = n_clustered - int(counts.sum())
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return counts


def _radius_factors(mix: dict, halos: int) -> np.ndarray:
    i = np.arange(1, halos + 1, dtype=np.float64)
    return (float(mix["radius_base"])
            + np.mod(i * float(mix["radius_step"]), 1.0))


def snapshot(mix: dict, chips: int, seed: int, k: int, b: float,
             pooled: bool = True) -> Snapshot:
    """Snapshot ``k`` of run ``seed``: ``particles_per_chip * chips`` points
    with velocities, and the FOF linking length for linking parameter b.
    With ``pooled`` False, positions come from ``(seed, k)`` even where the
    mix has a position pool."""
    n = particles(mix, chips)
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")

    key = (seed, int(k))
    if pooled and "position_keys" in mix:
        pool = [int(x) for x in mix["position_keys"]]
        if len(pool) != int(mix["snapshots"]):
            raise ValueError("a mix needs one position key per snapshot")
        order = np.random.default_rng((seed, _ORDER)).permutation(len(pool))
        key = (pool[order[int(k) % len(pool)]], 0)

    def rng(stream):
        return np.random.default_rng((*key, stream))

    counts = member_counts(mix, n)
    n_bg = n - int(counts.sum())
    parts = [rng(_BACKGROUND).uniform(0.0, 1.0, (n_bg, 3))]
    halo = np.concatenate([np.full(n_bg, -1, np.int32),
                           np.repeat(np.arange(len(counts), dtype=np.int32),
                                     counts)])
    if len(counts):
        margin = float(mix["center_margin"])
        centers = rng(_CENTERS).uniform(margin, 1.0 - margin, (len(counts), 3))
        factors = _radius_factors(mix, len(counts))
        m = rng(_MEMBERS)
        u = m.uniform(0.0, 1.0, (int(counts.sum()), 1))
        direction = m.standard_normal((int(counts.sum()), 3))
        direction /= np.maximum(np.linalg.norm(direction, axis=1,
                                               keepdims=True), 1e-9)
        scale = float(mix["halo_scale"]) * np.repeat(factors, counts)[:, None]
        r = np.maximum(scale * u ** float(mix["concentration_power"]),
                       float(mix["r_floor"]))
        parts.append(np.repeat(centers, counts, axis=0) + r * direction)
    pts = np.clip(np.concatenate(parts), 0.0, 1.0 - 1e-6).astype(np.float32)
    if "sort_axis" in mix:
        order = np.argsort(pts[:, int(mix["sort_axis"])], kind="stable")
        pts, halo = pts[order], halo[order]
    vel = (np.random.default_rng((seed, int(k), _VELOCITIES))
           .standard_normal((n, 3)).astype(np.float32))
    eps = b * (1.0 / n) ** (1.0 / 3.0)
    return Snapshot(points=pts, velocities=vel, eps=float(eps), halo=halo)
