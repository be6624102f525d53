"""``chip_smoke.py`` rehearsed off the chip: its one-chip phases run end to
end at a tiny n in this process and every check passes, but the status it
would print is not ok anywhere but on a TPU."""
from __future__ import annotations

import numpy as np

from conftest import load_chip_smoke as _chip_smoke


def test_one_chip_phases_pass_but_status_not_ok_off_tpu():
    from benchmarks.common import device_record

    cs = _chip_smoke()
    checks = cs.Checks()
    cs.run_one_chip(2048, 0, checks)
    names = [name for name, _ in checks.results]
    assert {"subsample_labels_vs_dbscan_ref", "labels_well_formed",
            "catalog_counts_and_flags", "catalog_pallas_vs_jax",
            "most_bound_in_own_halo", "so_masses_finite"} <= set(names)
    assert checks.ok, checks.results
    device = device_record()
    assert device["platform"] != "tpu"
    assert cs.final_status(checks, device) == {"ok": False, "device": device}


def test_status_ok_only_on_tpu_with_every_check_passing():
    cs = _chip_smoke()
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    checks = cs.Checks()
    assert not cs.final_status(checks, tpu)["ok"]   # no check ran
    checks.add("a", True)
    assert cs.final_status(checks, tpu)["ok"]
    assert not cs.final_status(checks, dict(tpu, platform="cpu"))["ok"]
    checks.add("b", False)
    assert not cs.final_status(checks, tpu)["ok"]


def test_ghost_capacity_holds_every_slab_boundary():
    cs = _chip_smoke()
    rng = np.random.default_rng(0)
    pts = np.sort(rng.uniform(0, 1, (4096, 3)).astype(np.float32), axis=0)
    eps = 0.01
    cap = cs._ghost_capacity(pts, eps, 4)
    assert cap % 1024 == 0
    for x in np.split(pts[:, 0], 4):
        assert (x <= x.min() + np.float32(eps)).sum() <= cap
        assert (x >= x.max() - np.float32(eps)).sum() <= cap
