"""``trace_reduce`` on a profiler trace recorded here on the CPU from a
tiny jitted function, and the peaks table."""

import json
import time

import bench_helpers
import numpy as np
import pytest

from bench import spans as S, trace_reduce as TR
from bench.harness import peaks_for

SLEEP_S = 0.08


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A window of three calls of a jitted function with a host sleep,
    under a program span ``host.sleep``, between the second and third."""
    import jax
    import jax.numpy as jnp

    from repro.obs import Tracer

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = np.ones((256, 256), np.float32)
    f(x).block_until_ready()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    tracer = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(TR.WINDOW), \
                tracer.span(TR.WINDOW):
            f(x).block_until_ready()
            f(x).block_until_ready()
            with tracer.span("host.sleep"):
                time.sleep(SLEEP_S)
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    profile = jax.profiler.ProfileData.from_file(TR.find_xplane(log_dir))
    spans = S.spans_from_snapshot(tracer.snapshot())
    win = S.named(spans, TR.WINDOW)[0]
    lo, _ = TR.window_of(profile)
    shift = lo * 1e-9 - win.start
    host = [S.Span(s.name, s.tid, s.start + shift, s.end + shift)
            for s in spans if s is not win]
    summary = TR.reduce_profile(profile, devices=[0], host_spans=host,
                                **bench_helpers.CPU_OPS)
    return profile, summary


def op_intervals(profile):
    lo, hi = TR.window_of(profile)
    sel, keep = bench_helpers.CPU_OPS["select"], \
        bench_helpers.CPU_OPS["op_filter"]
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            if sel(plane.name, line.name) is None:
                continue
            for ev in line.events:
                if keep(ev) and ev.end_ns > lo and ev.start_ns < hi:
                    out.append((max(ev.start_ns, lo), min(ev.end_ns, hi)))
    return out


def test_busy_is_the_union_of_op_intervals(recorded):
    profile, summary = recorded
    iv = op_intervals(profile)
    assert len(iv) >= 3
    assert summary.busy_s == pytest.approx(S.union_seconds(iv) * 1e-9)
    assert summary.busy_s <= sum(e - s for s, e in iv) * 1e-9 + 1e-12
    assert 0 < summary.busy_s < summary.window_s
    assert summary.busy_by_device == {0: summary.busy_s}


def test_idle_share_and_window(recorded):
    profile, summary = recorded
    lo, hi = TR.window_of(profile)
    assert summary.window_s == pytest.approx((hi - lo) * 1e-9)
    assert summary.window_s >= SLEEP_S
    assert summary.idle_share == pytest.approx(
        1 - summary.busy_s / summary.window_s)
    assert summary.idle_share > 0.5          # the sleep alone is most of it


def test_top_ops_carry_xla_names(recorded):
    _, summary = recorded
    names = [n for n, _ in summary.device_ops]
    assert names and all(n.startswith("jit_") and "/" in n for n in names)
    secs = [s for _, s in summary.device_ops]
    assert secs == sorted(secs, reverse=True)
    assert len(summary.device_ops) <= 10


def test_longest_idle_gap_is_labelled_with_the_open_span(recorded):
    _, summary = recorded
    label, secs = summary.idle_gaps[0]
    assert label == "host.sleep"
    assert secs >= SLEEP_S * 0.9
    gaps = [s for _, s in summary.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)


def test_device_without_ops_reads_zero_busy(recorded):
    profile, _ = recorded
    out = TR.reduce_profile(profile, devices=[0, 1],
                            **bench_helpers.CPU_OPS)
    assert out.busy_by_device[1] == 0.0
    assert out.busy_s == pytest.approx(out.busy_by_device[0] / 2)


def test_tpu_selector_takes_only_xla_ops_of_tpu_planes():
    assert TR.tpu_ops("/device:TPU:3", "XLA Ops") == 3
    assert TR.tpu_ops("/device:TPU:0", "XLA Modules") is None
    assert TR.tpu_ops("/device:TPU_NON_CORE:0", "XLA Ops") is None
    assert TR.tpu_ops("/host:CPU", "XLA Ops") is None


def test_missing_window_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        TR.find_xplane(str(tmp_path))


def test_peaks_known_kind_and_source():
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    doc = json.loads((bench_helpers.ROOT / "bench" / "peaks.json")
                     .read_text())
    assert "TPU v5e" in doc["source"] and doc["source_url"]


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99 imaginary")


def test_nested_ops_report_self_time():
    ops = [(0.0, 100.0, "m/while"), (10.0, 30.0, "m/fusion"),
           (40.0, 50.0, "m/fusion"), (120.0, 130.0, "m/copy")]
    out = {(s, n): t for s, _, n, t in TR._self_times(ops)}
    assert out[(0.0, "m/while")] == pytest.approx(70e-9)
    assert out[(10.0, "m/fusion")] == pytest.approx(20e-9)
    assert out[(120.0, "m/copy")] == pytest.approx(10e-9)


def test_tpu_op_named_by_module_and_short_op():
    class Ev:
        name = "%fusion.5 = s32[2048,512]{1,0} fusion(s32[32768,512] %x)"
        stats = [("device_offset_ps", "1")]

    mods = [(0.0, 10.0, "jit_a"), (20.0, 30.0, "jit_b")]
    starts = [0.0, 20.0]
    assert TR._op_name(Ev, TR._module_at(mods, starts, 25.0)) \
        == "jit_b/fusion.5"
    assert TR._module_at(mods, starts, 15.0) is None
    assert TR._op_name(Ev, None) == "fusion.5"
