"""The stage reduction (`bench.stages`): device self time by the program's
``smoother.*`` scopes, host self time by its ``serve.*`` spans, and idle
gaps named by the innermost span of either kind. Hand-made traces, a
hand-encoded trace file, and a trace recorded on a TPU v5e (the 8-track
fleet job of ``trace_small.json``, from the end of its first launch to
the start of its second, each op with its scope), kept as a fixture."""
import json
import os

import pytest

from bench import stages, tracing

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_scoped.json")


def _trace(ops, spans):
    """``ops``: (name, start, end, scope); ``spans``: (name, start, end,
    thread), a ``bench.window`` among them."""
    planes = ["/device:TPU:0"] if ops else []
    events = tracing.Events(
        {p: [tracing.Event(*o[:3]) for o in ops] for p in planes},
        [tracing.Event(*s[:3]) for s in spans])
    return stages.Trace(events, {p: [o[3] for o in ops] for p in planes},
                        [stages.Span(*s) for s in spans])


OPS = [("%while.1 = loop", 10, 60, "smoother.iterate"),
       ("%fusion.2 = f", 12, 20, "smoother.linearize"),
       ("%custom-call.3 = c", 20, 45, "smoother.cost"),
       ("%fusion.4 = f", 45, 55, "smoother.combine"),
       ("%copy.5 = c", 70, 75, stages.UNSCOPED),
       ("%fusion.6 = f", 90, 98, "smoother.loglik")]
SPANS = [("bench.window", 0, 100, 0),
         ("bench.serve_requests", 0, 100, 0),
         ("serve.launch", 2, 99, 0),
         ("serve.pad", 2, 8, 0),
         ("serve.dispatch", 8, 9, 0),
         ("serve.wait", 9, 76, 0),
         ("serve.unpack", 78, 88, 0),
         ("serve.launch", 30, 33, 1)]   # another thread: counted on its own


def test_scopes_are_self_times():
    got = stages.reduce(_trace(OPS, SPANS))
    assert got["scopes"] == pytest.approx({
        "smoother.iterate": 7e-9, "smoother.linearize": 8e-9,
        "smoother.cost": 25e-9, "smoother.combine": 10e-9,
        stages.UNSCOPED: 5e-9, "smoother.loglik": 8e-9})
    assert sum(got["scopes"].values()) == pytest.approx(got["busy_s"])


def test_host_spans_are_self_times_per_thread():
    got = stages.reduce(_trace(OPS, SPANS))["host_spans"]
    assert got == {"serve.launch": pytest.approx([(97 - 84 + 3) * 1e-9, 2]),
                   "serve.pad": pytest.approx([6e-9, 1]),
                   "serve.dispatch": pytest.approx([1e-9, 1]),
                   "serve.wait": pytest.approx([67e-9, 1]),
                   "serve.unpack": pytest.approx([10e-9, 1])}


def test_gaps_name_the_innermost_span_and_the_rest_is_unchanged():
    trace = _trace(OPS, SPANS)
    got = stages.reduce(trace)
    assert [(name, round(t * 1e9)) for name, t in got["idle_gaps"]] == [
        ("serve.unpack", 15), ("serve.pad", 10), ("serve.wait", 10),
        ("serve.launch", 2)]
    assert got["idle_by_span"] == {
        "serve.unpack": [pytest.approx(15e-9), 1],
        "serve.pad": [pytest.approx(10e-9), 1],
        "serve.wait": [pytest.approx(10e-9), 1],
        "serve.launch": [pytest.approx(2e-9), 1]}
    bench_only = trace.events._replace(host=[
        e for e in trace.events.host if e.name.startswith("bench.")])
    plain = tracing.reduce(bench_only)
    for key in ("busy_s", "window_s", "device_ops"):
        assert got[key] == plain[key]


def test_window_outside_clips_and_a_live_trace_reads_its_extent():
    ops = OPS + [("%fusion.7 = f", 120, 130, "smoother.filter")]
    got = stages.reduce(_trace(ops, SPANS))
    assert "smoother.filter" not in got["scopes"]
    live = stages.with_window(_trace(
        ops, [s for s in SPANS if s[0] != "bench.window"]))
    got = stages.reduce(live)
    assert got["window_s"] == pytest.approx(120e-9)
    assert got["scopes"]["smoother.filter"] == pytest.approx(10e-9)


def test_nothing_to_read():
    assert stages.reduce(_trace([], SPANS)) is None
    assert stages.reduce(_trace(OPS, SPANS[1:])) is None


@pytest.mark.parametrize("op_name,want", [
    ("jit(run)/jit(main)/smoother.iterate/while/body/smoother.cost/"
     "cholesky", "smoother.cost"),
    ("jit(run)/vmap(jvp(smoother.linearize))/mul", "smoother.linearize"),
    ("jit(run)/jit(main)/r_stack", stages.UNSCOPED),
])
def test_innermost_scope(op_name, want):
    assert stages.innermost_scope(op_name) == want


def _recorded():
    with open(FIXTURE) as fh:
        raw = json.load(fh)
    ops = {k: [tracing.Event(*o[:3]) for o in v]
           for k, v in raw["devices"].items()}
    spans = [stages.Span(*h) for h in raw["host"]]
    host = [tracing.Event(*h[:3]) for h in raw["host"]]
    host.append(tracing.Event(tracing.WINDOW_SPAN, *raw["window"]))
    return stages.Trace(tracing.Events(ops, host),
                        {k: [o[3] for o in v]
                         for k, v in raw["devices"].items()}, spans)


def test_recorded_scopes_add_up_to_device_self_time():
    trace = _recorded()
    got = stages.reduce(trace)
    lo, hi = next((e.start_ns, e.end_ns) for e in trace.events.host
                  if e.name == tracing.WINDOW_SPAN)
    self_time = sum(sum(tracing._self_times(ops, lo, hi).values())
                    for ops in trace.events.devices.values()) / 1e9
    assert sum(got["scopes"].values()) == pytest.approx(self_time,
                                                        rel=1e-12)
    assert set(got["scopes"]) == {stages.UNSCOPED, *(
        f"smoother.{s}" for s in ("iterate", "linearize", "filter",
                                  "smooth", "combine", "cost", "loglik"))}
    # One device, ops nested in whole: self times cover the busy time.
    assert self_time == pytest.approx(got["busy_s"], rel=1e-9)


def test_recorded_host_spans_nest_under_launches():
    trace = _recorded()
    got = stages.reduce(trace)["host_spans"]
    lo, hi = next((e.start_ns, e.end_ns) for e in trace.events.host
                  if e.name == tracing.WINDOW_SPAN)
    launches = sum(min(s.end_ns, hi) - max(s.start_ns, lo)
                   for s in trace.spans if s.name == "serve.launch") / 1e9
    assert sum(t for t, _ in got.values()) == pytest.approx(launches)
    assert set(got) == {"serve.launch", "serve.pad", "serve.dispatch",
                        "serve.wait", "serve.unpack"}
    assert got["serve.launch"][0] < 0.01 * launches


def test_recorded_gaps_name_serve_spans():
    trace = _recorded()
    got = stages.reduce(trace)
    bench_only = tracing.reduce(trace.events._replace(host=[
        e for e in trace.events.host if e.name.startswith("bench.")]))
    assert all(name.startswith("bench.") for name, _ in
               bench_only["idle_gaps"])
    assert all(name.startswith("serve.") for name, _ in got["idle_gaps"])
    assert [t for _, t in got["idle_gaps"]] == \
        [t for _, t in bench_only["idle_gaps"]]
    idle = sum(t for t, _ in got["idle_by_span"].values())
    assert idle == pytest.approx(got["window_s"] - got["busy_s"])
    assert set(got["idle_by_span"]) <= {
        "bench.serve_requests", "serve.launch", "serve.pad",
        "serve.dispatch", "serve.wait", "serve.unpack"}
    for key in ("busy_s", "window_s", "device_ops"):
        assert got[key] == bench_only[key]


# -- a trace file encoded by hand (XSpace protobuf) -------------------------

def _varint(n):
    out = bytearray()
    while True:
        low, n = n & 0x7F, n >> 7
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """Fields ``(number, value)``: an int as a varint, str or bytes as a
    length-delimited value."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, lines, events, stats=()):
    """An XPlane: ``events`` are XEventMetadata ``(id, name, op_name,
    by_ref)`` (``by_ref``: the op name is stat metadata 100's name, as a
    trace stores a shared string), ``stats`` extra XStatMetadata
    ``(id, name)``."""
    fields = [(2, name)]
    fields += [(3, line) for line in lines]
    for mid, text, op_name, by_ref in events:
        stat = ([] if op_name is None else
                [(5, _msg((1, 1), (7, 100)) if by_ref
                  else _msg((1, 1), (5, op_name)))])
        fields.append((4, _msg((1, mid), (2, _msg((1, mid), (2, text),
                                                   *stat)))))
    for sid, text in [(1, stages.OP_NAME_STAT), *stats]:
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, text))))))
    return _msg(*fields)


def _line(name, *events):
    """An XLine at t=1 ms: events ``(metadata id, offset us, length us)``."""
    return _msg((2, name), (3, 1_000_000), *(
        (4, _msg((1, mid), (2, off * 1_000_000), (3, dur * 1_000_000)))
        for mid, off, dur in events))


def _device_plane():
    return _plane("/device:TPU:0", [
        _line("XLA Modules", (9, 0, 100)),
        _line(tracing.OP_LINE, (1, 0, 50), (2, 10, 20), (3, 60, 10))],
        [(1, "%while.1 = loop", "jit(run)/smoother.iterate/while", False),
         (2, "%fusion.2 = f", "", True),
         (3, "%copy.3 = c", None, False),
         (9, "jit_run(1)", None, False)],
        stats=[(100, "jit(run)/smoother.iterate/while/body/"
                     "smoother.cost/cholesky")])


def test_op_names_come_from_the_trace_file(tmp_path):
    host = _plane("/host:CPU", [_line(
        "python", (1, 0, 100), (2, 0, 90), (3, 70, 20))],
        [(1, "bench.window", None, False), (2, "serve.launch", None, False),
         (3, "serve.unpack", None, False)])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, _device_plane()), (1, host)))
    assert stages.op_names(str(path)) == {"/device:TPU:0": [
        "jit(run)/smoother.iterate/while",
        "jit(run)/smoother.iterate/while/body/smoother.cost/cholesky", ""]}
    got = stages.reduce(stages.read_xplane(str(path)))
    assert got["scopes"] == pytest.approx({
        "smoother.iterate": 30e-6, "smoother.cost": 20e-6,
        stages.UNSCOPED: 10e-6})
    assert got["host_spans"] == {"serve.launch": pytest.approx([70e-6, 1]),
                                 "serve.unpack": pytest.approx([20e-6, 1])}
    assert got["idle_gaps"] == [["serve.unpack", pytest.approx(30e-6)],
                                ["serve.launch", pytest.approx(10e-6)]]


def test_command_line_reads_a_live_trace(tmp_path, capsys):
    """A trace of the live service has no ``bench.window``: the device
    ops' extent is the window."""
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_msg((1, _device_plane())))
    assert stages.main([str(tmp_path)]) == 0
    got = json.loads(capsys.readouterr().out)
    assert got["window_s"] == pytest.approx(70e-6)
    assert got["scopes"] == pytest.approx({
        "smoother.iterate": 30e-6, "smoother.cost": 20e-6,
        stages.UNSCOPED: 10e-6})
    assert stages.main([]) == 2
