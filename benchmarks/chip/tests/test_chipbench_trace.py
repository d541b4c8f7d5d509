"""The trace reduction on small recorded traces."""

import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
import reduce_trace

#: one chip; ns timestamps.  Window 50..1050; ops busy 100..300 (two that
#: overlap) and 500..600; the kernel runs 150..300
FWD = ('%jvp__.3 = f32[96,4,8,128]{3,2,1,0} custom-call(f32[96,64,16]{2,1,0}'
       ' %copy, f32[96,1,2]{2,1,0} %b), custom_call_target="tpu_custom_call"')
BWD = ('%transpose_jvp___.3 = f32[96,64,16]{2,1,0} custom-call(f32[96,64,16]'
       '{2,1,0} %copy), custom_call_target="tpu_custom_call"')
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
SMALL = {
    "ops": {"/device:TPU:0": [(FUSION, 100, 200),
                              (FWD, 150, 300),
                              (BWD, 500, 600),
                              (FUSION, 2000, 2100)]},     # after window
    "modules": {"/device:TPU:0": [("jit_step(1)", 100, 300),
                                  ("jit_tables(2)", 500, 600)]},
    "host": [("bench.window", 50, 1050), ("bench.step", 0, 400),
             ("bench.flush", 600, 1000)],
}


def test_busy_is_the_union_and_idle_its_complement():
    red = reduce_trace.reduce(SMALL)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(300e-9)
    assert red["idle_share"] == pytest.approx(0.7)
    assert red["chips"] == 1


def test_kernel_and_module_time_are_summed_by_name():
    red = reduce_trace.reduce(SMALL)
    assert reduce_trace.kernel_seconds(red, "fwd") == pytest.approx(150e-9)
    assert reduce_trace.kernel_seconds(red, "bwd") == pytest.approx(100e-9)
    assert reduce_trace.module_seconds(red, "jit_tables") == \
        pytest.approx(100e-9)
    assert red["device_ops"][0] == ["%jvp__.3 custom-call",
                                    pytest.approx(150e-9)]


def test_raster_passes_are_told_by_their_output_layout():
    assert reduce_trace.raster_pass(FWD) == "fwd"
    assert reduce_trace.raster_pass(BWD) == "bwd"
    assert reduce_trace.raster_pass(FUSION) is None
    other = ('%custom-call.4 = f32[22,3]{0,1} custom-call(f32[11,3]{0,1} '
             '%a), custom_call_target="ConcatBitcast"')
    assert reduce_trace.raster_pass(other) is None
    assert reduce_trace.short_name(FUSION) == "%fusion.1 fusion"


def test_idle_gaps_are_labelled_by_the_overlapping_span():
    red = reduce_trace.reduce(SMALL)
    gaps = red["idle_gaps"]
    assert gaps[0] == ["bench.flush", pytest.approx(450e-9)]   # 600..1050
    assert gaps[1] == ["bench.step", pytest.approx(200e-9)]    # 300..500
    assert gaps[2] == ["bench.step", pytest.approx(50e-9)]     # 50..100


def test_busy_is_averaged_over_chips():
    two = dict(SMALL, ops={"/device:TPU:0": [("a", 100, 600)],
                           "/device:TPU:1": [("a", 100, 200)]})
    red = reduce_trace.reduce(two)
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx(300e-9)


def test_a_recorded_host_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    tr = reduce_trace.load(reduce_trace.find_xplane(tmp_path))
    names = [n for n, _, _ in tr["host"]]
    assert "bench.window" in names and "bench.step" in names
    assert tr["ops"] == {}              # no TPU plane on this host
