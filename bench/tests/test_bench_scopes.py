"""Device time by the program's scopes and idle time by its host spans, on
a recorded v5e slice and on profiles written here byte by byte."""
import json
import os

import pytest

from bench import scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW = "bench.window"


@pytest.fixture(scope="module")
def recorded():
    """1.5 ms of a fused Averaging window (MLP, 4 clients, 2 chunks) recorded
    on a TPU v5e: the consumer waits on the staged chunk, then dispatches
    it, while the producer thread stages the next; the first chunk starts
    on the device at its end.  Device ops under 20 ns are left out."""
    with open(os.path.join(HERE, "trace_v5e_scoped.json")) as f:
        return scopes.Scoped.from_json(json.load(f))


@pytest.mark.parametrize("op_name, split", [
    ("jit(chunk)/while/body/closed_call/vmap(client)/transpose(jvp("
     "segment))/conv_general_dilated:", "client"),
    ("jit(step)/jvp(server)/head/dot_general:", "server"),
    ("jit(chunk)/while/body/closed_call/vmap(adam)/mul:", "optimizer"),
    ("jit(step)/client/adam/sqrt", "optimizer"),
    ("jit(chunk)/while/body/closed_call/cond/branch_1_fun/eq1/div:", "eq1"),
    ("jit(chunk)/while:", ""),
    ("jit(client_step)/add", ""),
    ("", ""),
])
def test_scope_of_takes_the_first_scope_by_priority(op_name, split):
    assert scopes.scope_of(op_name) == split


def test_the_old_fixtures_still_read():
    for name in ("trace_small.json", "trace_v5e.json"):
        with open(os.path.join(HERE, name)) as f:
            assert trace.Trace.from_json(json.load(f)).planes


def test_json_round_trip(recorded):
    again = scopes.Scoped.from_json(json.loads(json.dumps(
        recorded.to_json())))
    assert again.trace.planes == recorded.trace.planes
    assert again.op_names == recorded.op_names


def test_recorded_split_closes_on_busy(recorded):
    split = scopes.device_split(recorded, WINDOW)
    assert split["busy"] == pytest.approx(
        trace.reduce(recorded.trace, WINDOW).busy_s)
    assert all(split[k] > 0 for k in scopes.SPLITS)
    assert split["unscoped"] >= 0
    assert sum(split[k] for k in scopes.SPLITS) + split["unscoped"] == \
        pytest.approx(split["busy"])


def test_recorded_split_by_hand(recorded):
    """The scoped ops are leaves, so each split is the summed length of its
    ops inside the window."""
    _, w0, w1 = trace._find_window(recorded.trace, WINDOW)
    plane = "/device:TPU:0"
    want = dict.fromkeys(scopes.SPLITS, 0.0)
    for (_, start, dur), op_name in zip(
            recorded.trace.planes[plane][trace.OPS_LINE],
            recorded.op_names[plane]):
        key = scopes.scope_of(op_name)
        if key:
            want[key] += max(0.0, min(start + dur, w1) - max(start, w0)) * 1e-9
    split = scopes.device_split(recorded, WINDOW)
    assert {k: split[k] for k in scopes.SPLITS} == pytest.approx(want)


def test_recorded_idle_goes_to_the_consumer_phases(recorded):
    """The window's idle time is named by the consumer's program spans; the
    producer's ``repro.stage.*`` spans, on their own thread, name none."""
    gaps = scopes.phase_gaps(recorded, WINDOW)
    by = {}
    for label, s in gaps:
        by[label] = by.get(label, 0.0) + s
    assert set(by) == {"repro.stage.wait", "repro.fused.dispatch"}
    r = trace.reduce(recorded.trace, WINDOW)
    assert sum(by.values()) == pytest.approx(r.window_s - r.busy_s)
    # reduce() names the dispatch's gap by the runtime's span inside it
    assert "repro.fused.dispatch" not in dict(r.top_gaps())
    assert dict(r.top_gaps())["repro.stage.wait"] == pytest.approx(
        by["repro.stage.wait"])
    m = scopes.metrics(recorded, WINDOW, trace_rounds=1)
    assert m["idle_unattributed_share"] == 0.0
    assert m["device_unscoped_share"] == pytest.approx(
        100.0 * (1 - sum(m[f"device_ms.{k}"] for k in scopes.SPLITS)
                 / (1e3 * r.busy_s)))


def _made(host, ops, op_names):
    return scopes.Scoped(
        trace.Trace({"/device:TPU:0": {trace.OPS_LINE: ops},
                     "/host:CPU": {"main": host}}),
        {"/device:TPU:0": op_names})


def test_unattributed_idle_is_under_the_run_span_alone():
    """Gaps: [0, 100) under repro.train alone, [200, 300) under a phase
    whose gap the runtime's own span covers, [400, 1000) under the window
    alone."""
    made = _made(
        host=[(WINDOW, 0, 1000), ("repro.train", 0, 500),
              ("repro.fused.loss_sync", 150, 200),
              ("np.asarray(jax.Array)", 160, 180)],
        ops=[("fusion.1", 100, 100), ("fusion.2", 300, 100)],
        op_names=["jit(chunk)/vmap(client)/add:", "jit(chunk)/eq1/add:"])
    by = {}
    for label, s in scopes.phase_gaps(made, WINDOW):
        by[label] = by.get(label, 0.0) + s
    assert by == pytest.approx({"repro.train": 100e-9,
                                "repro.fused.loss_sync": 100e-9,
                                WINDOW: 600e-9})
    m = scopes.metrics(made, WINDOW, trace_rounds=2)
    assert m["idle_unattributed_share"] == pytest.approx(700 / 800 * 100)
    assert m["device_ms.client"] == pytest.approx(50e-6)
    assert m["device_ms.eq1"] == pytest.approx(50e-6)
    assert m["device_ms.server"] == 0.0
    assert m["device_unscoped_share"] == pytest.approx(0.0, abs=1e-9)


def test_program_counters_from_run_stats():
    made = _made(host=[(WINDOW, 0, 100)], ops=[("fusion.1", 0, 100)],
                 op_names=[""])
    fused = {"repro.train": {"s": 10.0, "n": 1},
             "repro.stage.fill": {"s": 0.4, "n": 8},
             "repro.stage.put": {"s": 0.2, "n": 8},
             "repro.fused.stack_carry": {"s": 0.5, "n": 1},
             "repro.fused.unstack_carry": {"s": 1.5, "n": 1}}
    m = scopes.metrics(made, WINDOW, 1, fused, rounds=32, window_s=10.0)
    assert m["device_unscoped_share"] == pytest.approx(100.0)
    assert m["stage_fill_ms_per_round"] == pytest.approx(12.5)
    assert m["stage_put_ms_per_round"] == pytest.approx(6.25)
    assert m["carry_swap_share"] == pytest.approx(20.0)
    assert "step_dispatch_ms" not in m
    ref = {"repro.train": {"s": 3.0, "n": 1},
           "repro.ref.client_step": {"s": 0.6, "n": 48},
           "repro.ref.server_step": {"s": 0.6, "n": 48},
           "repro.ref.loss_sync": {"s": 0.24, "n": 48}}
    m = scopes.metrics(made, WINDOW, 1, ref, rounds=4, window_s=3.0)
    assert m["step_dispatch_ms"] == pytest.approx(25.0)
    assert m["loss_sync_ms"] == pytest.approx(5.0)
    assert "carry_swap_share" not in m and "stage_fill_ms_per_round" not in m


# ------------------------------------------------ a profile written by hand
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of ``(number, value)``: an int is a varint, text
    or bytes a length-delimited field."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, lines, metadata, stat_names=()):
    """An XPlane; ``metadata``: id -> (event name, [(stat id, value)])."""
    fields = [(1, 1), (2, name)]
    for line_name, events in lines:
        fields.append((3, _msg((1, 1), (2, line_name), (3, 0), *[
            (4, _msg((1, mid), (2, start * 1000), (3, dur * 1000)))
            for mid, start, dur in events])))
    for mid, (event_name, stats) in metadata.items():
        value = _msg((1, mid), (2, event_name), *[
            (5, _msg((1, sid), (5 if isinstance(v, str) else 3, v)))
            for sid, v in stats])
        fields.append((4, _msg((1, mid), (2, value))))
    for sid, stat in stat_names:
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, stat))))))
    return _msg(*fields)


def test_load_reads_op_names_once_per_program_and_op(tmp_path):
    """Two programs hold an op of the same name; each event takes the
    ``op_name`` of the program whose ``XLA Modules`` event holds it."""
    same = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    device = _plane(
        "/device:TPU:0",
        lines=[("XLA Modules", [(1, 0, 100), (2, 200, 100)]),
               (trace.OPS_LINE, [(3, 10, 50), (4, 210, 50), (5, 260, 20),
                                 (3, 60, 30)])],
        metadata={1: ("jit_client_step(11)", []),
                  2: ("jit_server_step(22)", []),
                  3: (same, [(1, "jit(client_step)/client/add:"),
                             (2, 11)]),
                  4: (same, [(1, "jit(server_step)/server/adam/mul:"),
                             (2, 22)]),
                  5: ("%copy.2 = f32[8]{0} copy(f32[8]{0} %x)", [(2, 22)])},
        stat_names=[(1, "tf_op"), (2, "program_id")])
    host = _plane("/host:CPU",
                  lines=[("python3", [(1, 0, 300), (2, 100, 100)])],
                  metadata={1: (WINDOW, []), 2: ("repro.ref.loss_sync", [])})
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_msg((1, device), (1, host)))

    scoped = scopes.load(str(tmp_path))
    evs = scoped.trace.planes["/device:TPU:0"][trace.OPS_LINE]
    assert [e[0] for e in evs] == ["fusion.1", "fusion.1", "copy.2",
                                   "fusion.1"]
    assert scoped.op_names["/device:TPU:0"] == [
        "jit(client_step)/client/add:", "jit(server_step)/server/adam/mul:",
        "", "jit(client_step)/client/add:"]
    split = scopes.device_split(scoped, WINDOW)
    assert split["client"] == pytest.approx(80e-9)
    assert split["optimizer"] == pytest.approx(50e-9)
    assert split["unscoped"] == pytest.approx(20e-9)
    gaps = scopes.phase_gaps(scoped, WINDOW)
    assert [label for label, _ in gaps] == [WINDOW, "repro.ref.loss_sync",
                                            WINDOW]
    assert [s for _, s in gaps] == pytest.approx([10e-9, 120e-9, 20e-9])
