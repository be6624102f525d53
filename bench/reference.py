"""Plain reference for FOF halo finding, and the checks that decide `correct`.

Independent of the program under test: numpy and scipy only, in float64.
The same operations on the same particles give the same answers:

* FOF with linking length eps (DBSCAN with minPts = 2): particles i, j are
  linked when |x_i - x_j| <= eps; clusters are the connected components,
  each labelled by its smallest particle index; singletons are noise (-1).
* the halo catalog of a labelling: per cluster of at least ``min_count``
  members, in ascending label order, the count, center of mass, mean
  velocity, 3-D velocity dispersion and largest distance from the center;
* most-bound centers: per halo, the member of least potential
  ``phi_i = -sum_{j: |x_i - x_j| <= eps} 1 / sqrt(r_ij^2 + soft^2)``;
* spherical-overdensity masses: about a center, the radius where the mean
  enclosed density falls below ``delta`` times the mean density, found by
  the bisection the configuration states, and the count inside it.

A pair whose distance lies within ``AMBIGUOUS`` (relative, on r^2) of a
limit can be decided either way by float32 arithmetic; the checks accept
either decision for such pairs and nothing else.

Each ``check_*`` function returns a number that is 0 (or near it) for a
right answer; the configuration file states each number's limit.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components
from scipy.spatial import cKDTree

# Relative width on r^2 within which float32 may decide a pair either way:
# about 8x the rounding of a float32 squared distance (3 terms) and eps^2.
AMBIGUOUS = 4e-6


class Fof(NamedTuple):
    sure: np.ndarray        # (n,) component of the graph of sure links
    loose: np.ndarray       # (n,) component with ambiguous links added
    labels: np.ndarray      # (n,) int32 labels of the sure graph
    pairs: np.ndarray       # (m, 2) pairs with r^2 <= eps^2 (float64)
    r2: np.ndarray          # (m,) their squared distances


def _components(n: int, edges: np.ndarray) -> np.ndarray:
    g = coo_matrix((np.ones(len(edges), np.int8), (edges[:, 0], edges[:, 1])),
                   shape=(n, n))
    return connected_components(g, directed=False)[1]


def labels_from_components(comp: np.ndarray) -> np.ndarray:
    """Component ids -> labels: the smallest index of each component of two
    or more particles, -1 for a singleton."""
    n = len(comp)
    first = np.full(comp.max() + 1 if n else 0, n, np.int64)
    np.minimum.at(first, comp, np.arange(n))
    size = np.bincount(comp)
    return np.where(size[comp] >= 2, first[comp], -1).astype(np.int32)


def fof(points: np.ndarray, eps: float) -> Fof:
    p = np.asarray(points, np.float64)
    n = len(p)
    e2 = float(eps) ** 2
    cand = cKDTree(p).query_pairs(np.sqrt(e2 * (1 + AMBIGUOUS)),
                                  output_type="ndarray").reshape(-1, 2)
    r2 = np.sum((p[cand[:, 0]] - p[cand[:, 1]]) ** 2, axis=1)
    sure = cand[r2 < e2 * (1 - AMBIGUOUS)]
    a = _components(n, sure)
    b = _components(n, cand[r2 <= e2 * (1 + AMBIGUOUS)])
    inside = r2 <= e2
    return Fof(sure=a, loose=b, labels=labels_from_components(a),
               pairs=cand[inside], r2=r2[inside])


def neighbor_total(ref: Fof, n: int) -> int:
    """Sum over particles of |N_eps(i)|, the particle itself included."""
    return n + 2 * len(ref.pairs)


def _off_mode(group: np.ndarray, value: np.ndarray) -> np.ndarray:
    """True where ``value`` differs from the most common value of its
    ``group`` (ties go to the smaller value)."""
    if len(group) == 0:
        return np.zeros((0,), bool)
    key = np.stack([group, value], 1)
    uniq, inv, cnt = np.unique(key, axis=0, return_inverse=True,
                               return_counts=True)
    order = np.lexsort((uniq[:, 1], -cnt, uniq[:, 0]))
    g_sorted = uniq[order, 0]
    first = np.ones(len(order), bool)
    first[1:] = g_sorted[1:] != g_sorted[:-1]
    mode = dict(zip(g_sorted[first].tolist(), uniq[order[first], 1].tolist()))
    return value != np.array([mode[g] for g in group.tolist()])


def check_fof(labels: np.ndarray, ref: Fof) -> int:
    """Particles whose label no float32 decision of the ambiguous pairs
    explains: a linked particle marked noise or a lone one labelled; a
    label that splits a sure component or joins particles that no link
    joins; a cluster not labelled by its smallest index."""
    labels = np.asarray(labels).astype(np.int64).reshape(-1)
    n = len(labels)
    if n != len(ref.sure):
        return n
    a_size = np.bincount(ref.sure)[ref.sure]
    b_size = np.bincount(ref.loose)[ref.loose]
    bad = ((labels < 0) & (a_size >= 2)) | ((labels >= 0) & (b_size == 1))
    bad |= (labels >= n) | (labels < -1)
    linked = a_size >= 2
    bad[linked] |= _off_mode(ref.sure[linked], labels[linked])
    member = labels >= 0
    bad[member] |= _off_mode(labels[member], ref.loose[member])
    idx = np.flatnonzero(member)
    if len(idx):
        lab = labels[idx]
        first = np.full(n, n, np.int64)
        np.minimum.at(first, lab, idx)
        bad[idx] |= first[lab] != lab
    return int(bad.sum())


class Catalog(NamedTuple):
    root: np.ndarray    # (h,) ascending cluster labels
    count: np.ndarray   # (h,)
    center: np.ndarray  # (h, 3)
    vmean: np.ndarray   # (h, 3)
    vdisp: np.ndarray   # (h,)
    rmax: np.ndarray    # (h,)
    slot: np.ndarray    # (n,) halo slot per particle, -1 for none


def catalog(points, velocities, labels, min_count: int) -> Catalog:
    p = np.asarray(points, np.float64)
    v = np.asarray(velocities, np.float64)
    labels = np.asarray(labels).astype(np.int64)
    n = len(labels)
    roots, inv, count = np.unique(np.where(labels >= 0, labels, n),
                                  return_inverse=True, return_counts=True)
    keep = (roots < n) & (count >= min_count)
    slot_of = np.where(keep, np.cumsum(keep) - 1, -1)
    slot = slot_of[inv]
    h = int(keep.sum())
    cnt = count[keep].astype(np.int64)
    m = slot >= 0

    def seg_sum(x):
        out = np.zeros((h,) + x.shape[1:], np.float64)
        np.add.at(out, slot[m], x[m])
        return out

    center = seg_sum(p) / cnt[:, None]
    vmean = seg_sum(v) / cnt[:, None]
    ev2 = seg_sum(np.sum(v * v, axis=1)) / cnt
    vdisp = np.sqrt(np.maximum(ev2 - np.sum(vmean ** 2, axis=1), 0.0))
    rmax = np.zeros((h,), np.float64)
    np.maximum.at(rmax, slot[m],
                  np.linalg.norm(p[m] - center[slot[m]], axis=1))
    return Catalog(root=roots[keep], count=cnt, center=center, vmean=vmean,
                   vdisp=vdisp, rmax=rmax, slot=slot)


def check_catalog_counts(got: dict, ref: Catalog) -> int:
    """Integer disagreements: the halo count, each halo's root and member
    count, each particle's slot, and the overflow flag."""
    h = len(ref.root)
    nh = int(got["num_halos"])
    k = min(nh, h)
    bad = abs(nh - h) + int(bool(got["overflow"]))
    bad += int(np.sum(np.asarray(got["root"])[:k] != ref.root[:k]))
    bad += int(np.sum(np.asarray(got["count"])[:k] != ref.count[:k]))
    if "particle_halo" in got:
        bad += int(np.sum(np.asarray(got["particle_halo"]) != ref.slot))
    return bad


def check_catalog_values(got: dict, ref: Catalog, eps: float) -> float:
    """Largest error of a halo's center, largest radius (in units of eps),
    mean velocity or dispersion (in units of the velocity scale, 1) and
    mass (in particles)."""
    k = min(int(got["num_halos"]), len(ref.root))
    if k == 0:
        return 0.0
    errs = [
        np.abs(np.asarray(got["center"])[:k] - ref.center[:k]).max() / eps,
        np.abs(np.asarray(got["rmax"])[:k] - ref.rmax[:k]).max() / eps,
        np.abs(np.asarray(got["vmean"])[:k] - ref.vmean[:k]).max(),
        np.abs(np.asarray(got["vdisp"])[:k] - ref.vdisp[:k]).max(),
        np.abs(np.asarray(got["mass"])[:k] - ref.count[:k]).max(),
    ]
    return float(max(errs))


def potentials(points, ref: Fof, eps: float, softening: float) -> np.ndarray:
    """phi_i over the eps-neighbors, the particle itself included."""
    p = np.asarray(points, np.float64)
    s2 = float(softening) ** 2
    w = 1.0 / np.sqrt(ref.r2 + s2)
    phi = np.full(len(p), -1.0 / np.sqrt(s2))
    np.subtract.at(phi, ref.pairs[:, 0], w)
    np.subtract.at(phi, ref.pairs[:, 1], w)
    return phi


def check_centers(index, center, points, cat: Catalog, phi) -> float:
    """Worst relative potential gap between each halo's chosen center and
    its most bound member: 0 when the chosen one is the most bound; inf
    when it is not a member or its position is not the member's."""
    index = np.asarray(index).astype(np.int64)
    h = len(cat.root)
    if len(index) < h:
        return float("inf")
    idx = index[:h]
    if (idx < 0).any() or (idx >= len(points)).any():
        return float("inf")
    if (cat.slot[idx] != np.arange(h)).any():
        return float("inf")
    if not np.array_equal(np.asarray(center)[:h],
                          np.asarray(points)[idx].astype(np.asarray(center).dtype)):
        return float("inf")
    m = cat.slot >= 0
    best = np.full(h, np.inf)
    np.minimum.at(best, cat.slot[m], phi[m])
    return float(np.max((phi[idx] - best) / np.abs(best), initial=0.0))


def check_so(centers, r_delta, count, m_delta, bracketed, points, *,
             delta: float, r_max: float, iters: int, particle_mass=1.0,
             box_volume=1.0) -> int:
    """Halos whose SO answer is not a fixed point of the bisection stated
    by the configuration: the count is not the number of particles within
    the returned radius; the mass is not count x particle mass; the mean
    density at the radius is below delta x the mean density, or at one
    bisection step beyond it is not; or the bracket flag is wrong. Counts
    and densities at an ambiguous distance or density pass either way."""
    p = np.asarray(points, np.float64)
    c = np.asarray(centers, np.float64)
    r = np.asarray(r_delta, np.float64)
    k = np.asarray(count).astype(np.int64)
    rho = float(delta) * len(p) * particle_mass / box_volume
    step = float(np.float32(r_max)) / 2 ** iters
    t = AMBIGUOUS
    bad = 0
    for i in range(len(c)):
        d2 = np.sum((p - c[i]) ** 2, axis=1)

        def counts(radius):
            return (int(np.sum(d2 < radius ** 2 * (1 - t))),
                    int(np.sum(d2 <= radius ** 2 * (1 + t))))

        def dens(cnt, radius):
            return cnt * particle_mass / (4.0 / 3.0 * np.pi
                                          * max(radius, 1e-12) ** 3)

        lo, hi = counts(r[i])
        ok = lo <= k[i] <= hi
        ok &= float(m_delta[i]) == float(k[i]) * particle_mass
        if r[i] > 0:
            ok &= dens(hi, r[i]) >= rho * (1 - t)
        nxt = r[i] + step
        if nxt < float(np.float32(r_max)) * (1 - t):
            ok &= dens(counts(nxt)[0], nxt) < rho * (1 + t)
        e_lo, e_hi = counts(float(np.float32(r_max)))
        edge = float(np.float32(r_max))
        if dens(e_hi, edge) < rho * (1 - t):
            ok &= bool(bracketed[i])
        elif dens(e_lo, edge) >= rho * (1 + t):
            ok &= not bool(bracketed[i])
        bad += int(not ok)
    return bad


def so_bisect(points, centers, *, delta: float, r_max: float, iters: int,
              particle_mass=1.0, box_volume=1.0):
    """The configuration's SO bisection, plainly: ``iters`` halvings of
    [0, r_max] keeping the mean enclosed density at or above delta x the
    mean density. Returns (r_delta, count, bracketed)."""
    p = np.asarray(points, np.float64)
    rho = float(delta) * len(p) * particle_mass / box_volume
    r_out, c_out, b_out = [], [], []
    for c in np.asarray(centers, np.float64):
        d2 = np.sum((p - c) ** 2, axis=1)

        def dens(radius):
            return (np.sum(d2 <= radius ** 2) * particle_mass
                    / (4.0 / 3.0 * np.pi * max(radius, 1e-12) ** 3))

        lo, hi = 0.0, float(r_max)
        for _ in range(iters):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if dens(mid) >= rho else (lo, mid)
        r_out.append(lo)
        c_out.append(int(np.sum(d2 <= lo ** 2)))
        b_out.append(bool(dens(float(r_max)) < rho))
    return (np.asarray(r_out), np.asarray(c_out, np.int64),
            np.asarray(b_out))
