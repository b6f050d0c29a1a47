"""The trace reduction on a small synthetic XSpace."""
import pytest
from jax.profiler import ProfileData

from bench import trace

XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 500000 }
    events { metadata_id: 6 offset_ps: 0 duration_ps: 9000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%copy.1 = s32[8]{0} copy(s32[8]{0} %a)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = f32[4096]{0:T(1024)} fusion(f32[1024]{0} %t, s32[4096]{0:T(1024)S(1)} %i), kind=kCustom, calls=%fc.7" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.9 = f32[1024]{0} fusion(f32[1024]{0} %t, s32[4096]{0} %i, f32[4096]{0} %u), kind=kCustom, calls=%fc.9" } }
  event_metadata { key: 4 value { id: 4 name: "%sort.4 = (s32[64]{0}, s32[64]{0}) sort(s32[64]{0} %k, s32[64]{0} %v), dimensions={0}" } }
  event_metadata { key: 5 value { id: 5 name: "jit_step" } }
  event_metadata { key: 6 value { id: 6 name: "%while.3 = (s32[]{:T(128)}, f32[16]{0}) while((s32[]{:T(128)}, f32[16]{0}) %tuple), condition=%c, body=%b" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.run" } }
  event_metadata { key: 3 value { id: 3 name: "other" } }
}
"""


def test_reduce_synthetic_trace():
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    t = trace.from_profile(pd)
    assert [s.name for s in t.spans] == ["bench.window", "bench.run"]
    r = trace.reduce(t)
    # window [1000, 10000] ns; ops clipped to it: copy [1000, 1500],
    # gather fusion [1000, 3000], scatter fusion [4000, 5000], sort
    # [6000, 6500]; the while loop encloses them and counts for nothing
    assert r["window_s"] == pytest.approx(9e-6)
    assert r["busy_s"] == pytest.approx(3.5e-6)
    assert r["idle_share"] == pytest.approx(1 - 3.5 / 9)
    assert r["class_s"] == pytest.approx(
        {"other": 0.5e-6, "gather": 2e-6, "scatter": 1e-6, "sort": 0.5e-6})
    # gaps [3000, 4000] (inside bench.run), [5000, 6000] and [6500, 10000]
    assert dict(map(tuple, r["breakdown"]["idle_gaps"])) == pytest.approx(
        {"bench.window": 4.5e-6, "bench.run": 1e-6})
    top = r["breakdown"]["device_ops"][0]
    assert top[0].startswith("%fusion.7") and top[1] == pytest.approx(2e-6)


@pytest.mark.parametrize("name,cls", [
    ("%fusion.41 = s32[33554432]{0:T(1024)} fusion(s32[33554432]{0:T(1024)} "
     "%gte.242, s32[33554432]{0:T(1024)} %bcf.5), kind=kCustom, calls=%f.3",
     "gather"),
    ("%fusion.43 = s32[1048577]{0:T(1024)S(1)} fusion(s32[1048577]{0:T(1024)"
     "S(1)} %copy.31, s32[33554432]{0:T(1024)} %b.90, s32[33554432]{0:T(1024)}"
     " %fusion.42), kind=kCustom, calls=%f.6", "scatter"),
    ("%sort.2 = (s32[1048577]{0:T(1024)S(1)}, s32[1048577]{0:T(1024)S(1)}) "
     "sort(s32[1048577]{0:T(1024)S(1)} %g, s32[1048577]{0} %iota), "
     "dimensions={0}", "sort"),
    ("%cond.142 = (s32[2097152]{0:T(1024)}, pred[2097152]{0}) conditional("
     "s32[]{:T(128)} %c, (s32[2097152]{0:T(1024)}) %t)", "control"),
    ("%fusion.38 = s32[2097152]{0:T(1024)S(1)} fusion(s32[1048577]{0} %g, "
     "s32[1048577]{0} %iota.72, s32[]{:T(128)} %k), kind=kCustom", "other"),
    ("%fusion.49 = s32[31417472]{0:T(1024)} fusion(s32[31417472]{0:T(1024)} "
     "%gte.242, s32[31418368]{0:T(1024)} %pad_clamp_fusion.11), "
     "kind=kCustom", "gather"),
    ("%fusion.52 = s32[1048577]{0:T(1024)S(1)} fusion(s32[1048577]{0} %c.34,"
     " s32[31417472]{0} %f.50, s32[31417472]{0} %f.51), kind=kCustom",
     "scatter"),
    ("%fusion.9 = f32[131080]{0} fusion(f32[131080]{0} %t, s32[4194304]{0} "
     "%i), kind=kCustom", "scatter"),
    ("%reduce-window.83 = s32[262144,128]{0,1:T(8,128)} reduce-window(s32["
     "262144,128]{0,1:T(8,128)} %copy.27, s32[]{:T(128)} %c)", "other"),
])
def test_classify_hlo_text(name, cls):
    assert trace.classify(name) == cls


def test_window_span_is_required():
    t = trace.Trace(ops={"/device:TPU:0": [trace.Op("x", 0, 1)]},
                    spans=[])
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(t)


def test_peaks_table():
    v5e = trace.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        trace.peaks("TPU v99")
