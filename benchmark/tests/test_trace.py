"""The trace reduction on a small synthetic trace of known answer."""
import pytest
from jax.profiler import ProfileData

from benchmark import trace

# times in ns: the window is [1000, 11000); the device runs [1000, 3000),
# [2500, 4000) (overlapping) and [7000, 8000), and a loop holding the
# first two spans [1000, 4000) (busy, but no op); the host runs job.init
# over [4000, 5000), job.run over [5000, 8500) and job.fetch after.
_TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 3000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "scatter.7" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } }
  event_metadata { key: 3 value { id: 3 name: "jit_loop" } }
  event_metadata { key: 4 value { id: 4 name: "%while.1 = (s32[]{:T(128)}) while((s32[]) %t), body=%b" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 3500000 }
    events { metadata_id: 4 offset_ps: 8500000 duration_ps: 2500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "job.init" } }
  event_metadata { key: 3 value { id: 3 name: "job.run" } }
  event_metadata { key: 4 value { id: 4 name: "job.fetch" } }
}
"""


def test_busy_union_gaps_and_ops():
    s = trace.summarize(ProfileData.from_text_proto(_TEXT))
    assert s.window_s == pytest.approx(10e-6)
    # union of [1000,4000) and [7000,8000): 4000 ns; the op at 20000 is
    # outside the window
    assert s.busy_s == pytest.approx(4e-6)
    assert s.devices == 1
    assert s.ops == [("scatter.7", pytest.approx(3e-6)),
                     ("fusion.3", pytest.approx(1.5e-6))]
    # gaps [4000,7000) (job.init 1000 ns, job.run 2000 ns: run wins) and
    # [8000,11000) (job.fetch 2500 ns, job.run 500 ns)
    assert s.gaps == [("job.run", pytest.approx(3e-6)),
                      ("job.fetch", pytest.approx(3e-6))]


def test_short_name():
    assert trace.short_name(
        "%fusion.17 = f32[4194304]{0:T(1024)S(1)} fusion(s32[67108864]{0} "
        "%a), kind=kCustom") == "%fusion.17 = f32[4194304]"


def test_union_and_gaps_helpers():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.union_length([]) == 0
    assert trace.gaps_between([(2, 3), (1, 4)], 0, 10) == [(0, 1), (4, 10)]
    assert trace.gaps_between([(0, 10)], 0, 10) == []


def test_no_window_or_device_is_an_error():
    only_device = _TEXT.split("planes {\n  id: 2")[0]
    with pytest.raises(ValueError, match="window"):
        trace.summarize(ProfileData.from_text_proto(only_device))


@pytest.mark.parametrize("op,holds_ops", [
    ("%while.1 = (s32[]) while((s32[]) %t), body=%b", True),
    ("%conditional.2 = f32[8]{0} conditional(pred[] %p, f32[8]{0} %x), "
     "branch_computations={%t, %f}", True),
    ("%call.3 = f32[8]{0} call(f32[8]{0} %x), to_apply=%g", True),
    ("%custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %x), "
     "custom_call_target=\"tpu_custom_call\"", False),
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%f",
     False),
])
def test_containers(op, holds_ops):
    assert bool(trace.CONTAINER.search(op)) is holds_ops


def test_a_kernel_inside_a_loop_is_an_op():
    """A Pallas kernel (``custom-call``) counts as an op, as the loop
    around it does not."""
    kernel = ('%custom-call.7 = f32[8]{0} custom-call(f32[8]{0} %x), '
              'custom_call_target=\\"tpu_custom_call\\"')
    s = trace.summarize(ProfileData.from_text_proto(
        _TEXT.replace('name: "scatter.7"', f'name: "{kernel}"')))
    assert s.busy_s == pytest.approx(4e-6)
    assert s.ops == [("%custom-call.7 = f32[8]", pytest.approx(3e-6)),
                     ("fusion.3", pytest.approx(1.5e-6))]
