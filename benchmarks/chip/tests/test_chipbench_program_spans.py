"""The program's spans and scopes read from a small hand-written trace
whose idle gaps, span times and scoped device times are known exactly,
and the per-layer metrics built on them."""
import dataclasses
import os
import types

import pytest

import chipbench_path  # noqa: F401
from chipbench import program_spans, trace
from chipbench.harness import BENCH_DIR, load_module

# One chip, times in ns (offsets in ps). Host: the window is 100..1100;
# bench.call 100..700 holds repro.serve.dispatch 120..400 and
# repro.serve.tiers 400..690; bench.wait 700..1100 holds repro.gc.gen0
# 850..900; a compile at 300..310 and one before the window. Device ops:
# 200-300 serve.gather (tf_op as a string), 300-400 store.gather,
# 500-600 serve.forward (tf_op by reference), 650-680 permfl.grad under
# a transpose, 660-670 engine.eval, 1200-1300 engine.eval outside the
# window; idle gaps 100-200, 400-500, 600-650 and 680-1100, the last
# crossing tiers, call, wait, gc.gen0 and wait again.
HOST = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 600000 }
    events { metadata_id: 3 offset_ps: 700000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 120000 duration_ps: 280000 }
    events { metadata_id: 5 offset_ps: 400000 duration_ps: 290000 }
    events { metadata_id: 6 offset_ps: 850000 duration_ps: 50000 }
    events { metadata_id: 7 offset_ps: 300000 duration_ps: 10000 }
    events { metadata_id: 7 offset_ps: 0 duration_ps: 50000 }
    events { metadata_id: 8 offset_ps: 0 duration_ps: 1100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.call" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } }
  event_metadata { key: 4 value { id: 4 name: "repro.serve.dispatch" } }
  event_metadata { key: 5 value { id: 5 name: "repro.serve.tiers" } }
  event_metadata { key: 6 value { id: 6 name: "repro.gc.gen0" } }
  event_metadata { key: 7 value { id: 7 name: "backend_compile_and_load" } }
  event_metadata { key: 8 value { id: 8 name: "$engine.py:400 run" } }
}
"""
DEVICE = """
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 200000 duration_ps: 480000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 11 offset_ps: 200000 duration_ps: 100000 }
    events { metadata_id: 12 offset_ps: 300000 duration_ps: 100000 }
    events { metadata_id: 13 offset_ps: 500000 duration_ps: 100000 }
    events { metadata_id: 14 offset_ps: 650000 duration_ps: 30000 }
    events { metadata_id: 15 offset_ps: 660000 duration_ps: 10000 }
    events { metadata_id: 15 offset_ps: 1200000 duration_ps: 100000 }
  }
  event_metadata { key: 10 value { id: 10 name: "jit_step(1)" } }
  event_metadata { key: 11 value { id: 11 name: "%fusion.1 = s8[8]{0} fusion(s8[8]{0} %p)"
    stats { metadata_id: 30 str_value: "jit(step)/serve.gather/gather:" } } }
  event_metadata { key: 12 value { id: 12 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)"
    stats { metadata_id: 30 str_value: "jit(run)/while/body/store.gather/gather:" } } }
  event_metadata { key: 13 value { id: 13 name: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %q)"
    stats { metadata_id: 32 str_value: "fusion" }
    stats { metadata_id: 30 ref_value: 31 } } }
  event_metadata { key: 14 value { id: 14 name: "%copy.4 = f32[8]{0} copy(f32[8]{0} %r)"
    stats { metadata_id: 30 str_value: "jit(run)/permfl.grad/transpose(jvp(dot_general)):" } } }
  event_metadata { key: 15 value { id: 15 name: "%fusion.5 = f32[] fusion(f32[8]{0} %s)"
    stats { metadata_id: 30 str_value: "jit(run)/engine.eval/reduce_sum:" } } }
  stat_metadata { key: 30 value { id: 30 name: "tf_op" } }
  stat_metadata { key: 31 value { id: 31 name: "jit(step)/serve.forward/dot_general:" } }
  stat_metadata { key: 32 value { id: 32 name: "hlo_category" } }
}
"""
RUN = types.SimpleNamespace(stats={"batches": 2, "calls": 2})
CELL = types.SimpleNamespace(params={"rounds": 1,
                                     "hp": {"k_team": 1, "l_local": 2}})
# metric -> value read from the trace above (ns -> ms: x 1e-6)
EXPECTED = {
    "serve_host_idle_ms.serve": (80 + 160) * 1e-6 / 2,
    "host_gc_ms.serve": 50 * 1e-6,
    "engine_host_idle_ms_per_call.cohort": (80 + 160 + 50) * 1e-6 / 2,
    "serve_gather_device_ms.serve": 100 * 1e-6 / 2,
    "serve_forward_device_ms.serve": 100 * 1e-6 / 2,
    "cohort_eval_device_ms_per_call": 10 * 1e-6 / 2,
    "cohort_store_device_ms_per_call": 100 * 1e-6 / 2,
    "train_grad_device_ms_per_step.train": 30 * 1e-6 / 4,
}


def write_trace(tmp, text, name="a"):
    """Serialize the text proto where the harness writes its traces and
    return the reduced view of it."""
    from jax.profiler import ProfileData
    d = tmp / f"chipbench-trace-{name}" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace.view(ProfileData.from_file(str(path)), chips=1), path


@pytest.fixture(autouse=True)
def temp_dir(tmp_path, monkeypatch):
    import tempfile
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(program_spans, "_CACHE", {})
    return tmp_path


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       "test_metric_" + name.replace(".", "_"))


def test_idle_goes_to_the_innermost_span_at_each_instant(temp_dir):
    tv, _ = write_trace(temp_dir, HOST + DEVICE)
    assert program_spans.idle_by_span(tv) == pytest.approx({
        "bench.call": 20e-9 + 10e-9, "repro.serve.dispatch": 80e-9,
        "repro.serve.tiers": 100e-9 + 50e-9 + 10e-9,
        "bench.wait": 150e-9 + 200e-9, "repro.gc.gen0": 50e-9})
    # the harness's own view puts each whole gap under the bench.* event
    # at its midpoint
    assert dict(tv.idle_gaps()) == pytest.approx(
        {"bench.call": 250e-9, "bench.wait": 420e-9})
    assert program_spans.span_seconds(tv, "repro.gc.") == \
        pytest.approx(50e-9)
    assert program_spans.compiles(tv) == 1


def test_segments_label_each_piece_by_its_innermost_span():
    spans = [(0, 100, "bench.window"), (10, 50, "bench.call"),
             (20, 30, "repro.a"), (20, 25, "repro.b")]
    assert program_spans.segments(spans, 0, 100) == (
        [0, 10, 20, 25, 30, 50, 100],
        ["bench.window", "bench.call", "repro.b", "repro.a", "bench.call",
         "bench.window"])
    assert program_spans.segments([], 0, 10) == (
        [0, 10], ["outside any annotation"])


def test_scopes_from_string_and_referenced_stats(temp_dir):
    tv, path = write_trace(temp_dir, HOST + DEVICE)
    scopes = program_spans.op_scopes(path.read_bytes())["/device:TPU:0"]
    assert scopes["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %q)"] == \
        "jit(step)/serve.forward/dot_general:"
    assert len(scopes) == 5
    assert program_spans.scope_seconds(tv, "serve.gather") == \
        pytest.approx(100e-9)
    assert program_spans.scope_seconds(tv, "store.gather",
                                       "serve.gather") == \
        pytest.approx(200e-9)
    assert program_spans.scope_seconds(tv, "engine.eval") == \
        pytest.approx(10e-9)             # the op outside the window clipped


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_its_known_value(temp_dir, name):
    tv, _ = write_trace(temp_dir, HOST + DEVICE)
    assert reader(name).read(tv, RUN, CELL, None) == \
        pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_is_silent_without_spans_or_scopes(temp_dir, name):
    """A program that records no spans and names no scopes (as before
    this instrumentation) reads as nothing, and nothing raises."""
    host = HOST.replace('"repro.', '"other.')
    device = DEVICE.replace("metadata_id: 30 ", "metadata_id: 32 ")
    tv, _ = write_trace(temp_dir, host + device)
    assert reader(name).read(tv, RUN, CELL, None) is None


def test_a_trace_of_another_window_is_refused(temp_dir):
    tv, _ = write_trace(temp_dir, HOST + DEVICE)
    with pytest.raises(ValueError, match="window"):
        program_spans.load(dataclasses.replace(tv, lo=tv.lo + 1))


def test_the_newest_trace_of_the_window_is_read(temp_dir):
    _, old = write_trace(temp_dir, HOST + DEVICE, "old")
    tv, new = write_trace(temp_dir, HOST.replace("repro.gc.gen0",
                                                 "repro.gc.gen1") + DEVICE,
                          "new")
    os.utime(old, (1, 1))
    assert "repro.gc.gen1" in program_spans.idle_by_span(tv)
