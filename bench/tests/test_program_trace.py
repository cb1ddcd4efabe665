"""Program spans and device scopes in a trace (``bench/program_trace.py``)
and the per-layer readers built on them: self time of nested device ops
and of nested spans, exact integer arguments, idle time per host span,
the protobuf decoder against JAX's own reader, and a trace recorded on
one TPU v5e."""
import shutil
from pathlib import Path

import pytest

from bench import harness, program_trace as pt, trace
from bench.jobs import JobRecord
from bench.program_trace import Op, Span

DATA = Path(__file__).parent / "data"
METRICS = harness.BENCH / "metrics"


def window(lo, hi):
    return Span("bench.window", lo, hi, 1, {})


def test_nested_device_ops_are_counted_once():
    # A while loop [0, 100) holding two body ops, the first holding an op
    # without a framework name (a compiler-added relayout), then an op of
    # no scope and an unnamed op outside any other.
    ops = [Op("graphyti.gather", 0, 100), Op("graphyti.chunk_scan", 10, 40),
           Op(None, 20, 30), Op("graphyti.chunk_scan", 50, 70),
           Op("other", 120, 130), Op(None, 150, 155)]
    r = pt.reduce_program({"/device:TPU:0": ops}, [window(0, 200)],
                          "bench.window")
    assert r["device_scopes"] == {
        "graphyti.gather": pytest.approx(50e-9),
        "graphyti.chunk_scan": pytest.approx(50e-9),
        "other": pytest.approx(15e-9)}
    busy = trace.reduce_events(
        {"/device:TPU:0": [trace.Event("op", o.start_ns, o.end_ns)
                           for o in ops]},
        [trace.Event("bench.window", 0, 200)], "bench.window")["busy_s"]
    assert sum(r["device_scopes"].values()) == pytest.approx(busy)


def test_overlapping_ops_and_the_window_edges():
    # Not nested: b starts inside a and outlives it; c crosses the window.
    ops = [Op("a", 0, 10), Op("b", 5, 15), Op("c", 18, 40)]
    got = pt.self_times(ops, 2, 30)
    assert got == {"a": 3, "b": 10, "c": 12}
    assert sum(got.values()) == 25  # the union of the ops in [2, 30]


def test_device_scopes_average_over_devices():
    r = pt.reduce_program({"/device:TPU:0": [Op("x", 0, 100)],
                           "/device:TPU:1": [Op("x", 0, 50)]},
                          [window(0, 100)], "bench.window")
    assert r["device_scopes"]["x"] == pytest.approx(75e-9)


def test_span_self_time_less_child_spans_on_the_same_thread():
    host = [window(0, 1000),
            Span("graphyti.superstep", 100, 500, 1, {"it": 0}),
            Span("graphyti.plan", 120, 200, 1, {"live": 3, "units": 9}),
            Span("graphyti.sync", 130, 150, 1, {}),
            Span("graphyti.stage", 220, 300, 1, {"bytes": 8, "units": 2}),
            # another thread: not a child of the superstep
            Span("graphyti.enqueue", 150, 400, 2, {}),
            Span("graphyti.superstep", 600, 700, 1, {"it": 1}),
            Span("DoEnqueueProgram", 610, 650, 1, {})]
    r = pt.reduce_program({"/device:TPU:0": [Op("x", 0, 1000)]}, host,
                          "bench.window")
    sp = r["spans"]
    assert set(sp) == {"graphyti.superstep", "graphyti.plan",
                       "graphyti.sync", "graphyti.stage", "graphyti.enqueue"}
    assert sp["graphyti.superstep"]["count"] == 2
    assert sp["graphyti.superstep"]["total_s"] == pytest.approx(500e-9)
    # 400 - (80 + 80) on the first, 100 on the second: JAX's own events
    # are not program spans.
    assert sp["graphyti.superstep"]["self_s"] == pytest.approx(340e-9)
    assert sp["graphyti.plan"]["self_s"] == pytest.approx(60e-9)
    assert sp["graphyti.enqueue"]["self_s"] == pytest.approx(250e-9)
    assert sp["graphyti.superstep"]["args"] == {"it": 1}
    assert sp["graphyti.plan"]["args"] == {"live": 3, "units": 9}


def test_span_outside_the_window_is_left_out():
    host = [window(100, 200), Span("graphyti.stage", 10, 20, 1, {"bytes": 5}),
            Span("graphyti.stage", 150, 160, 1, {"bytes": 7})]
    r = pt.reduce_program({"/device:TPU:0": []}, host, "bench.window")
    assert r["spans"]["graphyti.stage"]["count"] == 1
    assert r["spans"]["graphyti.stage"]["args"] == {"bytes": 7}


def test_integer_arguments_sum_exactly_above_int32():
    big = 3 * 2**31 + 7
    host = [window(0, 100)] + [
        Span("graphyti.stage", 10 * i, 10 * i + 5, 1, {"bytes": big})
        for i in range(5)]
    r = pt.reduce_program({"/device:TPU:0": []}, host, "bench.window")
    total = r["spans"]["graphyti.stage"]["args"]["bytes"]
    assert type(total) is int and total == 5 * big


def test_idle_under_sums_to_the_idle_time_of_the_window():
    host = [window(0, 1000), Span("bench.job", 0, 900, 1, {}),
            Span("graphyti.superstep", 100, 600, 1, {}),
            Span("graphyti.plan", 250, 420, 1, {}),
            Span("graphyti.stage", 500, 560, 1, {})]
    ops = [Op("x", 0, 200), Op("y", 300, 350), Op("z", 400, 520),
           Op("w", 700, 800)]
    r = pt.reduce_program({"/device:TPU:0": ops}, host, "bench.window")
    # Gaps [200,300) and [350,400) fall under the plan; [520,700) and
    # [800,1000) under the job (midpoints 610, past the superstep, and 900,
    # the job's inclusive end).
    assert r["idle_under"] == {
        "bench.job": pytest.approx(380e-9),
        "graphyti.plan": pytest.approx(150e-9)}
    red = trace.reduce_events(
        {"/device:TPU:0": [trace.Event(o.scope, o.start_ns, o.end_ns)
                           for o in ops]},
        [trace.Event(s.name, s.start_ns, s.end_ns) for s in host],
        "bench.window")
    idle = red["window_s"] - red["busy_s"]
    assert sum(r["idle_under"].values()) == pytest.approx(idle)
    # The ten longest gaps of trace.reduce_events are named alike.
    for name, seconds in red["idle_gaps"]:
        assert r["idle_under"][name] >= seconds - 1e-15


def test_reduce_refuses_a_trace_without_window_or_device():
    with pytest.raises(ValueError, match="no device plane"):
        pt.reduce_program({}, [window(0, 1)], "bench.window")
    with pytest.raises(ValueError, match="one host span"):
        pt.reduce_program({"/device:TPU:0": []}, [], "bench.window")


def test_innermost_scope():
    assert pt.innermost_scope(
        "jit(seg)/while/body/graphyti.gather/graphyti.dense/"
        "graphyti.chunk_scan/while/body/cond/branch_1_fun/scatter-add") \
        == "graphyti.chunk_scan"
    assert pt.innermost_scope("jit(<lambda>)/dot_general") == "other"
    assert pt.innermost_scope(None) is None
    assert pt.innermost_scope("") is None


def test_decoder_agrees_with_jax_on_the_recorded_tiny_trace(tmp_path):
    path = tmp_path / "tpu_tiny.xplane.pb"
    shutil.copy(DATA / "tpu_tiny.xplane.pb", path)
    devices, host = trace.read_xplane(str(path))
    pdev, phost = pt.read_program_trace(str(path))
    assert list(pdev) == list(devices)
    # Only the fusion carries a framework name (jit(<lambda>)/dot_general).
    assert {o.scope for o in pdev["/device:TPU:0"]} == {"other", None}
    for ours, theirs in zip(pdev["/device:TPU:0"], devices["/device:TPU:0"]):
        assert ours.start_ns == pytest.approx(theirs.start_ns, abs=1)
        assert ours.end_ns == pytest.approx(theirs.end_ns, abs=2)
    assert sorted(s.name for s in phost) == sorted(e.name for e in host)
    r = pt.reduce_program(pdev, phost, "bench.window")
    busy = trace.reduce_events(devices, host, "bench.window")["busy_s"]
    assert r["device_scopes"] == {"other": pytest.approx(busy, rel=1e-3)}
    assert list(path.parent.iterdir()) == [path]  # it writes nothing


def test_recorded_tpu_program_trace(tmp_path):
    """A trace recorded on one TPU v5e: a ``residency='host'`` BFS and one
    device pull PageRank iteration on an RMAT scale-10 graph (512-edge
    chunks), each a ``bench.job`` inside ``bench.window``; the device,
    host and ``/host:metadata`` planes were kept."""
    path = tmp_path / "tpu_program.xplane.pb"
    shutil.copy(DATA / "tpu_program.xplane.pb", path)
    devices, host = pt.read_program_trace(str(path))
    assert list(devices) == ["/device:TPU:0"]
    r = pt.reduce_program(devices, host, "bench.window")
    scopes = r["device_scopes"]
    assert max(scopes, key=scopes.get) == "graphyti.chunk_scan"
    assert {"graphyti.dense", "graphyti.frontier"} <= set(scopes)
    assert scopes["other"] < 0.02 * scopes["graphyti.chunk_scan"]
    sp = r["spans"]
    assert sp["graphyti.superstep"]["count"] == 5  # the BFS's supersteps
    assert sp["graphyti.plan"]["args"] == {"live": 87, "units": 205}
    # 9 batches of 16 chunks: int32 source and destination ids per slot
    # plus one valid byte per chunk.
    assert sp["graphyti.stage"]["count"] == 9
    assert sp["graphyti.stage"]["args"] == {"units": 87, "bytes": 9 * 65552}
    assert sp["graphyti.enqueue"]["count"] == 9
    assert sp["graphyti.segment"]["args"] == {"stop": 1}  # PageRank
    assert sp["graphyti.sync"]["count"] > 0
    red = trace.reduce_events(*trace.read_xplane(str(path)), "bench.window")
    assert sum(scopes.values()) == pytest.approx(red["busy_s"], rel=1e-3)
    assert sum(r["idle_under"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-3)


def test_control_flow_takes_its_scope_from_the_hlo_proto():
    # The profiler gives loops and conditionals no tf_op stat; the
    # program's HloProto on the /host:metadata plane names them.
    b = (DATA / "tpu_program.xplane.pb").read_bytes()
    planes = [pt._plane(b, s) for f, s in pt._fields(b, 0, len(b)) if f == 1]
    (meta,) = [m for name, _, m, _ in planes if name == pt.METADATA_PLANE]
    names = pt._hlo_op_names(b, meta)
    loops = {op for ops in names.values() for inst, op in ops.items()
             if inst.startswith("while")}
    assert "jit(run)/graphyti.chunk_scan/while" in loops  # host kernel
    assert any(op.endswith("graphyti.dense/graphyti.chunk_scan/while")
               for op in loops)  # the device superstep's scans


def run_data(trace_dict, records=(1000,), supersteps=(1,), padded=4096):
    jobs = [JobRecord(1.0, None, s, r, None)
            for s, r in zip(supersteps, records)]
    return harness.RunData(jobs, [None] * len(jobs), 10, 20, padded,
                           {"hbm_bytes_per_s": 1.0}, trace_dict)


def base_trace(**extra):
    return {"busy_s": 0.9, "window_s": 2.0, "device_ops": [],
            "idle_gaps": [], **extra}


def reader(name):
    return harness.metric_reader(name, METRICS)


def test_scan_ns_per_record_reader():
    read = reader("scan_ns_per_record.pr")
    scopes = {"graphyti.chunk_scan": 0.5, "other": 0.1}
    assert read(run_data(base_trace(device_scopes=scopes),
                         records=(2000, 3000), supersteps=(1, 1))) \
        == pytest.approx(0.5e9 / 5000)
    # No reading: no program scopes (a program without them), no scan
    # scope, no records, or records that int32 could have wrapped.
    assert read(run_data(base_trace())) is None
    assert read(run_data(None)) is None
    assert read(run_data(base_trace(device_scopes={"other": 1.0}))) is None
    assert read(run_data(base_trace(device_scopes=scopes),
                         records=(0,))) is None
    assert read(run_data(base_trace(device_scopes=scopes),
                         supersteps=(600,), padded=2**22)) is None


def test_host_link_gb_per_s_reader():
    read = reader("host_link_gb_per_s.bfs")
    spans = {"graphyti.stage": {"count": 3, "total_s": 0.1, "self_s": 0.1,
                                "args": {"bytes": 3 * 2**31, "units": 48}}}
    assert read(run_data(base_trace(spans=spans))) \
        == pytest.approx(3 * 2**31 / 2.0 / 1e9)
    assert read(run_data(base_trace())) is None
    assert read(run_data(base_trace(spans={}))) is None


def test_plan_idle_reader():
    read = reader("plan_idle.bfs")
    spans = {"graphyti.plan": {"count": 2, "total_s": 0.2, "self_s": 0.2,
                               "args": {}}}
    idle = {"graphyti.plan": 0.05, "bench.job": 0.5}
    got = read(run_data(base_trace(spans=spans, idle_under=idle)))
    assert got == pytest.approx(2.5)
    assert got <= reader("device_idle.bfs")(run_data(base_trace()))
    # A plan span that never had the device idle under it reads 0.
    assert read(run_data(base_trace(spans=spans, idle_under={}))) == 0.0
    # No plan span at all (a device-resident run, or a program without
    # spans): no reading.
    assert read(run_data(base_trace(spans={}, idle_under=idle))) is None
    assert read(run_data(base_trace())) is None
