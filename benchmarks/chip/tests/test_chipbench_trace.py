"""The trace reduction on a small hand-written trace whose busy time,
idle time, kernel time and gaps are known exactly."""
import pytest

import chipbench_path  # noqa: F401
from chipbench import trace

# One chip. Host: the window is 100..1100 ns; a "bench.call" covers
# 100..700 and a "bench.wait" 700..1100. Device ops (ns): 200-400
# (prox kernel), 300-500 (fusion, overlapping it), 600-650 (prox), a
# while 150-900 spanning its body, and one op outside the window.
XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 600000 }
    events { metadata_id: 3 offset_ps: 700000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 50000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.call" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 150000 duration_ps: 750000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 11 offset_ps: 200000 duration_ps: 200000 }
    events { metadata_id: 12 offset_ps: 300000 duration_ps: 200000 }
    events { metadata_id: 11 offset_ps: 600000 duration_ps: 50000 }
    events { metadata_id: 13 offset_ps: 150000 duration_ps: 750000 }
    events { metadata_id: 12 offset_ps: 1200000 duration_ps: 100000 }
  }
  event_metadata { key: 10 value { id: 10 name: "jit_step(123)" } }
  event_metadata { key: 11 value { id: 11 name: "%prox_sgd_flat.7 = (f32[20,128]{1,0:T(8,128)}, f32[20,128]{1,0:T(8,128)}) custom-call(f32[1,2]{1,0} %a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 12 value { id: 12 name: "%fusion.3 = f32[8,32]{1,0:T(8,128)} fusion(f32[8,32]{1,0} %p), kind=kLoop" } }
  event_metadata { key: 13 value { id: 13 name: "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b" } }
}
"""


@pytest.fixture(scope="module")
def tv():
    from jax.profiler import ProfileData
    return trace.view(ProfileData.from_text_proto(XSPACE), chips=1)


def test_window_and_busy(tv):
    assert tv.window_s == pytest.approx(1000e-9)
    # union of 150-900 (the while spans the rest)
    assert tv.busy_s("/device:TPU:0") == pytest.approx(750e-9)
    assert tv.busy_mean_s() == pytest.approx(750e-9)
    assert tv.max_idle_share() == pytest.approx(0.25)


def test_kernel_and_module_time(tv):
    assert tv.op_seconds("%prox_sgd_flat", "custom-call(") == \
        pytest.approx(250e-9)
    assert tv.op_seconds("%fusion.3") == pytest.approx(200e-9)  # 1 clipped
    assert tv.module_seconds() == pytest.approx(750e-9)
    assert tv.module_count("jit_step") == 1


def test_top_ops_leave_out_control_flow(tv):
    names = [n for n, _ in tv.top_ops()]
    assert names[0].startswith("%prox_sgd_flat.7 = (f32[20,128], f32[20,128])")
    assert names[0].endswith("custom-call")
    assert names[1] == "%fusion.3 = f32[8,32] fusion"
    assert not any("while" in n for n in names)


def test_idle_gaps_labelled_by_host(tv):
    gaps = dict(tv.idle_gaps())
    # idle 100-150 under bench.call, 900-1100 under bench.wait
    assert gaps == pytest.approx({"bench.call": 50e-9, "bench.wait": 200e-9})


def test_interval_arithmetic():
    assert trace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace.union_ns([(0, 10), (5, 15), (20, 21)]) == 16
    assert trace.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert trace.gaps([(0, 12)], 0, 10) == []


def test_missing_window_or_device_is_refused():
    from jax.profiler import ProfileData
    no_window = XSPACE.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError, match="bench.window"):
        trace.view(ProfileData.from_text_proto(no_window), chips=1)
    with pytest.raises(ValueError, match="TPU:1"):
        trace.view(ProfileData.from_text_proto(XSPACE), chips=2)
