"""The program-trace readings on synthetic interval lists."""

import types

import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
import harness
import program_trace as pt
import reduce_trace

#: one chip; ns.  Window 0..1000.  A while (assign) 100..400 holds two body
#: ops 120..200 (assign) and 250..300 (unscoped); a raster op 500..600
OPS = [("assign", 100, 400), ("assign", 120, 200), ("unscoped", 250, 300),
       ("raster", 500, 600)]
#: host: step 0..1000 holds put 400..450 and sync 600..900; flush 950..990
SPANS = [("gs.fit.step", 0, 1000), ("gs.fit.put", 400, 450),
         ("gs.fit.sync", 600, 900), ("gs.serve.flush", 950, 990)]


def _run(window_s, **kw):
    return types.SimpleNamespace(red={"window_s": window_s}, **kw)


def test_layer_is_the_innermost_scope():
    assert pt.layer_of("jit(step)/jvp(shard_map)/gs.assign/while/body/add") \
        == "assign"
    assert pt.layer_of("transpose(jvp(gs.loss))/gs.raster/raster_bwd") \
        == "raster"
    assert pt.layer_of("jit(step)/vmap(gs.project)/mul") == "project"
    assert pt.layer_of("jit(step)/jvp()/gs.raster/gs.gather/gather:") \
        == "gather"
    assert pt.layer_of("jit(step)/gs.assignment_like/add") == "unscoped"
    assert pt.layer_of("") == "unscoped"


def test_nested_ops_count_self_time_once():
    got = pt.self_seconds(OPS, 0, 1000)
    # the while's 300 ns less its two body ops (80 + 50): 170 ns, plus 80
    assert got["assign"] == pytest.approx(250e-9)
    assert got["unscoped"] == pytest.approx(50e-9)
    assert got["raster"] == pytest.approx(100e-9)
    assert sum(got.values()) == pytest.approx(400e-9)   # busy time


def test_self_time_is_clipped_to_the_window():
    got = pt.self_seconds(OPS, 150, 550)
    # the clipped while 150..400 less its body ops (50 + 50), plus 50
    assert got["assign"] == pytest.approx(200e-9)
    assert got["raster"] == pytest.approx(50e-9)


def test_idle_goes_to_the_innermost_open_span():
    got = pt.idle_seconds(OPS, SPANS, 0, 1000)
    # idle: 0..100, 400..500, 600..1000
    assert got["gs.fit.put"] == pytest.approx(50e-9)        # 400..450
    assert got["gs.fit.sync"] == pytest.approx(300e-9)      # 600..900
    assert got["gs.serve.flush"] == pytest.approx(40e-9)    # 950..990
    assert got["gs.fit.step"] == pytest.approx(
        (100 + 50 + 50 + 10) * 1e-9)    # 0..100, 450..500, 900..950, 990..1000
    assert "none" not in got
    assert sum(got.values()) == pytest.approx(600e-9)


def test_idle_outside_every_span_goes_to_none():
    got = pt.idle_seconds(OPS, [("gs.fit.put", 400, 450)], 0, 1000)
    assert got["gs.fit.put"] == pytest.approx(50e-9)
    assert got["none"] == pytest.approx(550e-9)
    assert pt.idle_seconds(OPS, [], 0, 1000) == {
        "none": pytest.approx(600e-9)}


def test_tables_are_averaged_over_chips():
    tr = {"ops": {"/device:TPU:0": OPS, "/device:TPU:1": [("assign", 0, 1000)]},
          "spans": SPANS, "window": (0, 1000)}
    t = pt.reduce(tr)
    assert t["window_s"] == pytest.approx(1e-6)
    assert t["scope_s"]["assign"] == pytest.approx((250e-9 + 1000e-9) / 2)
    assert t["idle_s"]["gs.fit.sync"] == pytest.approx(150e-9)
    assert t["spans"] == sorted(n for n, _, _ in SPANS)


def _stub(monkeypatch, tr):
    monkeypatch.setattr(pt, "_MEMO", {})
    monkeypatch.setattr(pt, "find_newest", lambda root=None: "trace.xplane.pb")
    monkeypatch.setattr(pt, "load", lambda path: tr)


def test_readers_divide_per_step_and_per_request(monkeypatch):
    _stub(monkeypatch, {"ops": {"/device:TPU:0": OPS}, "spans": SPANS,
                        "window": (0, 1000)})
    steps = _run(1000 * 1e-9, notes={"window_steps": 4})
    served = _run(1000 * 1e-9, work={"served": 2})
    assign = harness.metric_reader("assign_ms.train")(steps)
    assert assign == pytest.approx(1e3 * 250e-9 / 4)
    train = harness.metric_reader("host_stall_ms.train")(steps)
    assert train == pytest.approx(1e3 * (600e-9 - 40e-9) / 4)
    serve = harness.metric_reader("host_stall_ms.serve")(served)
    assert serve == pytest.approx(1e3 * 40e-9 / 2)


def test_a_window_mismatch_returns_none(monkeypatch):
    _stub(monkeypatch, {"ops": {"/device:TPU:0": OPS}, "spans": SPANS,
                        "window": (0, 1000)})
    assert pt.for_run(_run(2000 * 1e-9)) is None
    assert harness.metric_reader("assign_ms.train")(
        _run(2000 * 1e-9, notes={"window_steps": 4})) is None
    assert pt.for_run(_run(1000 * 1e-9)) is not None


def test_a_program_without_names_reads_none(monkeypatch):
    """The parent program: no scopes on its ops, no gs.* spans."""
    _stub(monkeypatch, {"ops": {"/device:TPU:0": [("unscoped", 0, 500)]},
                        "spans": [], "window": (0, 1000)})
    run = _run(1000 * 1e-9, notes={"window_steps": 4}, work={"served": 2})
    for name in ("assign_ms.train", "host_stall_ms.train",
                 "host_stall_ms.serve"):
        assert harness.metric_reader(name)(run) is None


def test_no_trace_reads_none(monkeypatch, tmp_path):
    monkeypatch.setattr(pt, "_MEMO", {})
    assert pt.for_run(_run(1e-6), root=tmp_path) is None


def test_a_recorded_host_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("gs.serve.flush", n=1):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = pt.find_newest(tmp_path)
    tr = pt.load(path)
    assert [n for n, _, _ in tr["spans"]] == ["gs.serve.flush"]
    assert tr["ops"] == {}              # no TPU plane on this host
    # the same window, to the ns, as the reducer reads it
    assert tr["window"] == reduce_trace.window_of(reduce_trace.load(path))


#: a TPU plane as the profiler writes it: the scope path in the event
#: metadata's ``tf_op`` stat, as a string or as a reference to a name
TPU_PLANE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500999 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 100000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 700000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = while()"
    stats { metadata_id: 10 str_value: "jit(step)/gs.assign/while" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = fusion()"
    stats { metadata_id: 10 ref_value: 11 } } }
  event_metadata { key: 3 value { id: 3 name: "%raster_bwd.1 = custom-call()"
    stats { metadata_id: 10
            str_value: "jit(step)/transpose(jvp(gs.raster))/raster_bwd" } } }
  event_metadata { key: 4 value { id: 4 name: "jit_step(1)" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11
                  name: "jit(step)/gs.assign/while/body/fusion" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "gs.fit.sync" } }
}
"""


def test_device_ops_take_their_scope_from_the_event_metadata(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TPU_PLANE))
    tr = pt.load(path)
    assert tr["ops"] == {"/device:TPU:0": [
        ("assign", 1000, 1500), ("assign", 1100, 1300),
        ("raster", 1600, 1700)]}
    assert tr["spans"] == [("gs.fit.sync", 1500, 1600)]
    assert tr["window"] == (1000, 2000)
    # the ns the reducer reads from the same file
    red = reduce_trace.load(path)
    assert [iv[1:] for iv in red["ops"]["/device:TPU:0"]] == \
        [iv[1:] for iv in tr["ops"]["/device:TPU:0"]]
    t = pt.reduce(tr)
    # the while's 300 ns of its own and its body op's 200, both assign
    assert t["scope_s"] == {"assign": pytest.approx(500e-9),
                            "raster": pytest.approx(100e-9)}
    assert t["idle_s"] == {"gs.fit.sync": pytest.approx(100e-9),
                           "none": pytest.approx(300e-9)}
