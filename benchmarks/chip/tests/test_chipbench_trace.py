"""The trace reduction on a small synthetic trace, worked by hand."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import smoke  # noqa: F401  (puts the harness on sys.path)
from chipbench.trace import reduce_planes


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def planes(second_device: bool = False):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 100, 1000),          # window [100, 1100]
        ev("bench.next_batch", 300, 100),       # [300, 400]
        ev("bench.step_loop", 150, 900),        # [150, 1050]
        ev("PjitFunction(train_step)", 160, 5),  # not a harness span
    ])])
    ops = [ev("fusion.1", 50, 150),       # [50, 200] -> clipped to [100, 200]
           ev("fusion.2", 180, 70),       # [180, 250] overlaps fusion.1
           ev("dot.3", 420, 380),         # [420, 800]
           ev("fusion.1", 900, 100)]      # [900, 1000]
    mods = [ev("jit_train_step", 150, 700), ev("jit_decode_step", 880, 150),
            ev("jit_decode_step", 1090, 50)]   # ends past the window
    dev0 = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                           NS(name="XLA Modules", events=mods),
                                           NS(name="Steps", events=[])])
    out = [host, NS(name="/device:TPU:0 SparseCore", lines=[]), dev0]
    if second_device:
        out.append(NS(name="/device:TPU:1", lines=[
            NS(name="XLA Ops", events=[ev("dot.3", 100, 1000)])]))
    return out


def test_busy_union_gaps_and_attribution():
    s = reduce_planes(planes())
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [100,250] + [420,800] + [900,1000] = 150 + 380 + 100
    assert s.busy_s == pytest.approx(630e-9)
    # gaps: [250,420] mid 335 in next_batch (innermost, started last);
    # [800,900] mid 850 in step_loop; [1000,1100] mid 1050 at the edge of
    # step_loop [150,1050] -> step_loop
    gaps = dict((k, v) for k, v in s.idle_gaps)
    assert gaps == pytest.approx({"bench.next_batch": 170e-9,
                                  "bench.step_loop": 200e-9})
    ops = dict((k, v) for k, v in s.device_ops)
    assert ops["fusion.1"] == pytest.approx(200e-9)   # 100 clipped + 100
    assert ops["dot.3"] == pytest.approx(380e-9)
    assert s.module_time("decode_step") == (1, pytest.approx(150e-9))
    assert s.module_time("train_step") == (1, pytest.approx(700e-9))


def test_busy_is_averaged_over_chips():
    s = reduce_planes(planes(second_device=True))
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((630e-9 + 1000e-9) / 2)


def test_a_trace_without_window_or_device_is_refused():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        reduce_planes(p)
    with pytest.raises(ValueError):
        reduce_planes([planes()[0]])


@pytest.mark.parametrize("hlo, label", [
    ("%copy.41 = bf16[1,16,2304]{2,1,0:T(8,128)(2,1)} copy(bf16[1,16,2304]"
     "{2,1,0} %constant_dynamic-slice_fusion.4)", "%copy.41 copy bf16[1,16,2304]"),
    ("%while.16 = (s32[]{:T(128)}, f32[4,2560]{1,0:T(4,128)}) while((s32[], "
     "f32[4,2560]) %tuple.338), condition=%c, body=%b", "%while.16 while"),
    ("fusion.1", "fusion.1"),
])
def test_op_label_keeps_name_opcode_and_shape(hlo, label):
    from chipbench.trace import op_label
    assert op_label(hlo) == label


def test_device_ops_are_summed_under_their_short_label():
    p = planes()
    ops = p[2].lines[0].events
    ops.append(ev("%dot.9 = f32[8,8]{1,0} dot(f32[8,8] %a, f32[8,8] %b)",
                  820, 50))
    s = reduce_planes(p)
    assert dict(s.device_ops)["%dot.9 dot f32[8,8]"] == pytest.approx(50e-9)
