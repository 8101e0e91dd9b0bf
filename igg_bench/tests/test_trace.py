"""The reduction from trace to per-layer numbers, on a trace recorded on the
chip (PR 22: 13 calls of `diffusion256.fused`, 3 s, flattened by
`trace.from_xplane`) and on synthetic events with known answers."""

import os

import pytest

from igg_bench import roofline, trace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "testdata")
DATA = os.path.join(TESTDATA, "diffusion256.fused.events.json.gz")


def test_recorded_chip_trace():
    ev = trace.load_events(DATA)
    r = trace.reduce(ev, 1)
    assert r.window_s == pytest.approx(3.171369394)
    # 13 calls x 250 k=4 groups, every one a Mosaic kernel launch
    assert r.n_kernel_ops == 13 * 250
    assert 0 < r.busy_s <= r.window_s
    assert r.kernel_s + r.glue_s == pytest.approx(r.busy_s, rel=2e-3)
    assert r.exchange_s == 0.0  # one block, no halo activity
    assert 0 <= r.idle_share_max < 0.05
    # the kernel's HBM roofline share from the committed byte function
    share = roofline.hbm_share(13 * 250 * 3 * 256**3 * 4, 819e9, r.kernel_s)
    assert 25 < share < 40
    # the one glue op per group: a whole-array copy in front of the kernel
    assert r.top_ops[1][0] == "copy (copy, glue)"


def test_recorded_four_chip_trace():
    """One call of `diffusion256x4.piped` on the 2x2 mesh (PR 22): ring and
    interior kernel passes, slab exchanges under `igg_slab_exchange_*`, and
    the collectives in flight (recorded on chip 0 only)."""
    ev = trace.load_events(os.path.join(TESTDATA, "diffusion256x4.piped.events.json.gz"))
    assert sorted(d for d, v in ev.in_flight.items() if v) == [0]
    r = trace.reduce(ev, 4)
    # 250 k=4 groups a call, each a ring and an interior launch, on 4 chips
    assert r.n_kernel_ops == 4 * 250 * 2
    assert 0 < r.exchange_exposed_s < r.exchange_s < r.window_s
    assert r.kernel_s + r.glue_s < r.busy_s <= r.window_s
    assert any("igg_slab_exchange_finish" in k and k.endswith("exchange)")
               for k, _ in r.top_ops)
    assert any("igg_interior_pass" in k and k.endswith("kernel)") for k, _ in r.top_ops)


def test_instruction_text_is_parsed():
    text = ('%closed_call.4 = f32[256,256,256]{2,1,0:T(8,128)} custom-call('
            'f32[256,256,256]{2,1,0:T(8,128)} %copy.13), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(f)/while/body/pallas_call"}')
    idx = trace.hlo_index(text)
    assert idx["closed_call.4"] == ("custom-call", "tpu_custom_call",
                                    "jit(f)/while/body/pallas_call")
    tup = ('%while = (s32[]{:T(128)}, f32[8]{0}) while((s32[]{:T(128)}, f32[8]{0})'
           ' %t), condition=%c, body=%b')
    assert trace.hlo_index(tup)["while"][0] == "while"


def test_synthetic_events():
    """Two chips.  Chip 0: kernels 0-4 and 6-10, a collective in flight 1-5
    whose done op runs 4-5 on the core (1 of the exchange exposed), glue
    10-11.  Chip 1: one kernel 0-6.  Window 0-12."""
    insts = {"k": ("custom-call", "tpu_custom_call", ""),
             "x": ("collective-permute-start", "", ""),
             "d": ("collective-permute-done", "", ""),
             "s": ("fusion", "", "jit(f)/igg_slab_exchange_begin/slice"),
             "g": ("copy", "", ""), "w": ("while", "", "")}
    ops = {0: [("w", 0, 12), ("k", 0, 4), ("s", 3.5, 4), ("d", 4, 5), ("k", 6, 10),
               ("g", 10, 11)],
           1: [("k", 0, 6)]}
    ev = trace.Events(ops, insts, [("igg_bench.wait", 4, 12)], (0, 12),
                      {0: [("x", 1, 5)]})
    r = trace.reduce(ev, 2)
    ns = 1e-9
    assert r.kernel_s == pytest.approx((8 + 6) / 2 * ns)
    # read on chip 0 alone, the one chip whose transfers in flight are known
    assert r.exchange_s == pytest.approx(4 * ns)  # 1-5: in flight and done
    assert r.exchange_exposed_s == pytest.approx(1 * ns)  # 4-5
    assert r.glue_s == pytest.approx(1 / 2 * ns)
    assert r.busy_s == pytest.approx((10 + 6) / 2 * ns)
    assert r.idle_share_max == pytest.approx(6 / 12)  # chip 1
    assert r.idle_gaps[0][0] == "host: igg_bench.wait"
    assert trace.Events.from_json(ev.to_json()) == ev


def test_union_and_minus():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert trace.minus(u, trace.union([(2, 6)])) == 3 - 1 + 3 - 1
