"""The reduction from a profiler trace to the device readings."""
import glob

import jax
import jax.numpy as jnp
import pytest

from bench import tracing
from bench.tracing import Ev


def test_union_and_idle_share():
    ops = {"/device:TPU:0": [Ev("a.1", 0, 10), Ev("b.2", 5, 15),
                             Ev("c.3", 30, 40)]}
    s = tracing.summarize(ops, [], (0, 50))
    assert s.busy_s == pytest.approx(25e-9)
    assert s.window_s == pytest.approx(50e-9)
    assert s.idle_share == pytest.approx(0.5)


def test_window_clips_and_chips_average():
    ops = {"/device:TPU:0": [Ev("a", -10, 10)],
           "/device:TPU:1": [Ev("a", 0, 30)]}
    s = tracing.summarize(ops, [], (0, 20))
    assert s.busy_s == pytest.approx(15e-9)      # (10 + 20) / 2 chips
    assert s.kernel_s["a"] == pytest.approx(15e-9)


def test_nested_ops_are_charged_self_time():
    evs = [Ev("while.1", 0, 100), Ev("fusion.2", 10, 30),
           Ev("spconv_gather_gemm.3", 40, 90, op="jit(f)/pallas_call")]
    s = tracing.summarize({"/device:TPU:0": evs}, [], (0, 100))
    assert s.kernel_s["while"] == pytest.approx(30e-9)
    assert s.kernel_s["fusion"] == pytest.approx(20e-9)
    assert s.kernel_s["spconv_gather_gemm"] == pytest.approx(50e-9)
    assert s.pallas_s == pytest.approx(50e-9)
    assert s.xla_s == pytest.approx(50e-9)
    assert s.busy_s == pytest.approx(100e-9)


@pytest.mark.parametrize("name,op,kernel,pallas", [
    ("spconv_gather_gemm.12", "", "spconv_gather_gemm", True),
    ("segment_sum_pallas.3", "", "segment_sum_pallas", True),
    ("custom-call.7", "jit(run)/jit(k)/pallas_call", "custom-call", True),
    ("fusion.1", "jit(run)/add", "fusion", False),
    ("copy-start", "", "copy-start", False),
    # on the TPU an event is named by its whole HLO instruction
    ('%spconv_gather_gemm.37 = f32[262144,128]{1,0:T(8,128)} custom-call('
     's32[27,2048,1,128]{3,2,1,0} %reshape.91), custom_call_target='
     '"tpu_custom_call"', "", "spconv_gather_gemm", True),
    ("%fusion.12 = f32[2048,64]{1,0:T(8,128)} fusion(f32[2048,64]{1,0} "
     "%param.3), kind=kLoop", "", "fusion", False),
])
def test_kernel_names(name, op, kernel, pallas):
    e = Ev(name, 0, 1, op)
    assert (e.kernel, e.pallas) == (kernel, pallas)


def test_idle_gaps_named_by_innermost_span():
    spans = [Ev("bench/window", 0, 100), Ev("bench/request", 10, 60),
             Ev("bench/request", 60, 100)]
    ops = {"/device:TPU:0": [Ev("k.1", 20, 30), Ev("k.2", 40, 50),
                             Ev("k.3", 70, 95)]}
    s = tracing.summarize(ops, spans, (0, 100))
    gaps = dict(s.idle_gaps)
    assert gaps["bench/window (before device work)"] == pytest.approx(10e-9)
    assert gaps["bench/request (before device work)"] == pytest.approx(
        20e-9)   # 10-20 and 60-70
    assert gaps["bench/request (between device ops)"] == pytest.approx(10e-9)
    assert gaps["bench/request (after device work)"] == pytest.approx(15e-9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_profile_recorded_on_the_cpu(tmp_path):
    @jax.jit
    def f(x):
        return jnp.sin(x) @ x

    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/request"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    prof = tracing.load(str(tmp_path))
    spans = tracing.host_spans(prof)
    assert sum(s.name == "bench/request" for s in spans) == 3
    win = tracing.window_of(spans, "bench/window")
    assert win is not None
    assert tracing.device_ops(prof) == {}, "a CPU trace has no TPU plane"
    ops = tracing.cpu_ops(prof)
    assert ops, "no operations found in the CPU trace"
    s = tracing.summarize(ops, spans, win)
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_share < 1
    assert any("sine" in k for k in s.kernel_s), s.kernel_s
    assert s.pallas_s == 0 and s.xla_s > 0
    assert len(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)) == 1
