"""The share arithmetic on the cells' own shapes: at or under 100% for any
kernel time at or above the byte bound."""

import pytest

from igg_bench import harness, roofline

CELLS = ("diffusion256.fused", "diffusion256x4.piped", "diffusion256.fused.periodz")


@pytest.mark.parametrize("cell", CELLS)
def test_share_at_most_100_on_real_shapes(cell):
    import json

    spec = json.load(open(f"{harness.ROOT}/BENCHMARK.json"))
    c = harness.load_cell(harness.ROOT, spec, cell)
    peak = harness.load_json(harness.ROOT, "igg_bench/peaks.json")["devices"][
        "TPU v5 lite"]["hbm_bytes_per_s"]
    least = c.solver.least_bytes_per_group(c.cfg, c.grid)
    bound = least / peak
    assert least >= 3 * 256**3 * 4
    for factor in (1.0, 1.0001, 2.5, 40.0):
        share = roofline.hbm_share(least, peak, bound * factor)
        assert share <= 100.0 + 1e-9
    assert roofline.hbm_share(least, peak, bound) == pytest.approx(100.0)


def test_porous_least_bytes():
    """T, Pf read; Pf written; three staggered fluxes read and written."""
    from igg_bench.solvers import porous

    g = harness.Grid((256, 256, 256), (1, 1, 1), (2, 2, 2), (False,) * 3, 4)
    faces = 3 * 257 * 256 * 256
    assert porous.least_bytes_per_group({}, g) == (3 * 256**3 + 2 * faces) * 4
    assert porous.pt_groups(10, 6) == [6, 4]
    assert porous.pt_groups(11, 6) == [6, 4]
