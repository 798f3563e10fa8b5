"""Model work counts against the published formulas, and the roof table."""

import os

import pytest

import harness


@pytest.mark.parametrize("n", [1, 512, 8192, 16384])
def test_gesv_is_hpl_count(n):
    w = harness.work("gesv")
    # HPL 2.3's own operation count for one right-hand side
    assert w.ops(n, 1) == pytest.approx(2 / 3 * n**3 + 3 / 2 * n**2,
                                        rel=1e-15)
    # LAWN 41 getrs adds 2 n^2 per further right-hand side
    assert w.ops(n, 16) - w.ops(n, 1) == pytest.approx(30 * n**2, rel=1e-15)
    assert w.bytes_moved(n, 1, 8) == 8 * (2 * n * n + 2 * n)


@pytest.mark.parametrize("n,nrhs", [(1, 1), (100, 3), (8192, 16)])
def test_posv_is_lawn41_count(n, nrhs):
    w = harness.work("posv")
    potrf_mults = n**3 / 6 + n**2 / 2 + n / 3
    potrf_adds = n**3 / 6 - n / 6
    potrs = nrhs * (n * n + n) + nrhs * (n * n - n)
    assert w.ops(n, nrhs) == pytest.approx(potrf_mults + potrf_adds + potrs,
                                           rel=1e-15)


def test_posv_8192_matches_the_issue():
    assert harness.work("posv").ops(8192, 16) == pytest.approx(185.4e9,
                                                               rel=1e-3)
    assert harness.work("gesv").ops(8192, 1) == pytest.approx(366.6e9,
                                                              rel=1e-3)


def test_roof_known_kind():
    r = harness.roof("TPU v5 lite")
    assert r["flops_per_s"] == 1.97e14 and r["bytes_per_s"] == 8.19e11


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_roof_refuses_unknown_kind(kind):
    with pytest.raises(harness.HarnessError):
        harness.roof(kind)


def test_roofs_name_their_source():
    t = harness.load_json(os.path.join(harness.BENCH, "roofs.json"))
    assert "TPU v5e" in t["source"]
