"""A traced call's device time by the program's scopes, and its idle time by
the program's host spans.

The program names its work (docs/ENGINES.md, "Reading a run"): each side's
loss and gradient run under ``jax.named_scope("client")`` / ``("server")``,
Adam under ``adam`` and Eq. (1) under ``eq1``, and the engines' host phases
are ``repro.*`` spans (``repro/obs.py``).  ``bench/trace.py`` reads device
ops by name alone; this module adds, for each device op, its HLO
``op_name``: the path of scopes, which the profile keeps as the ``tf_op``
stat of the op's event metadata.  That metadata is read once per distinct
(program, op) pair, never per event: each op event is placed in its
program by the ``XLA Modules`` event that holds it in time, whose name
ends in the program id, ``jit_chunk(<id>)``.  A fusion's ``op_name`` is that
of its root instruction.  ``jax.profiler.ProfileData`` does not expose
event metadata, so :func:`op_names` walks the ``.xplane.pb`` itself, down
to each device plane's metadata and no further (the ``XPlane`` schema of
``tsl/profiler/protobuf/xplane.proto``).

    python3 bench/scopes.py <profile dir> <window span> <rounds>

prints a recorded profile's split (``python3 bench/scopes.py`` alone: the
options).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

if __package__ in (None, ""):                  # run as a script
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import trace  # noqa: E402

MODULES_LINE = "XLA Modules"
#: scope names by priority, and the split each counts toward.  ``adam`` runs
#: inside a side's step and ``eq1`` inside the round, so they win
SCOPES = (("eq1", "eq1"), ("adam", "optimizer"), ("client", "client"),
          ("server", "server"))
SPLITS = tuple(key for _, key in SCOPES)
PROGRAM = "repro."
TRAIN = "repro.train"


# ------------------------------------------------------------ the xplane file
def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    ``(start, stop)`` of the bytes for anything else."""
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield number, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _entries(buf, span) -> Tuple[int, Tuple[int, int]]:
    """A map entry: (key, the value message's bytes)."""
    key, value = 0, (0, 0)
    for number, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def op_names(path: str) -> Dict[str, Dict[Tuple[str, str], str]]:
    """For each device plane of the ``.xplane.pb`` at ``path``:
    ``{(program id, op event name): op_name}`` of every op whose metadata
    carries one (XPlane: 2 name, 4 event_metadata, 5 stat_metadata;
    XEventMetadata: 2 name, 5 stats; XStat: 1 metadata_id, 5 str_value,
    3/4 uint64/int64_value)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[Tuple[str, str], str]] = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pn, v in _fields(buf, *plane):
            if pn == 2:
                name = _text(buf, v)
                if not name.startswith(trace.DEVICE_PREFIX):
                    break
            elif pn == 4:
                metas.append(_entries(buf, v)[1])
            elif pn == 5:
                sid, smeta = _entries(buf, v)
                for sn, sv in _fields(buf, *smeta):
                    if sn == 2:
                        stat_names[sid] = _text(buf, sv)
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        table = out.setdefault(name, {})
        for meta in metas:
            op, stats = "", {}
            for mn, mv in _fields(buf, *meta):
                if mn == 2:
                    op = _text(buf, mv)
                elif mn == 5:
                    sid, value = 0, None
                    for xn, xv in _fields(buf, *mv):
                        if xn == 1:
                            sid = xv
                        elif xn == 5:
                            value = _text(buf, xv)
                        elif xn in (3, 4):
                            value = str(xv)
                    stats[stat_names.get(sid)] = value
            if stats.get("tf_op") and stats.get("program_id"):
                table[(stats["program_id"], op)] = stats["tf_op"]
    return out


# -------------------------------------------------------------- the reading
@dataclass
class Scoped:
    """A trace as ``bench/trace.py`` reads it, and for each device plane the
    ``op_name`` of each of its ``XLA Ops`` events, in order ("" where the
    profile gives none)."""
    trace: trace.Trace
    op_names: Dict[str, List[str]] = field(default_factory=dict)

    def to_json(self) -> dict:
        return {**self.trace.to_json(), "op_names": self.op_names}

    @classmethod
    def from_json(cls, obj: dict) -> "Scoped":
        return cls(trace.Trace.from_json(obj), obj["op_names"])


def _program(module_event: str) -> str:
    """``jit_chunk(123)`` -> ``123``."""
    m = re.search(r"\((\d+)\)$", module_event)
    return m.group(1) if m else ""


def load(log_dir: str) -> Scoped:
    """What ``trace.load`` reads, with each device op's ``op_name``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {paths}")
    names = op_names(paths[0])
    data = ProfileData.from_file(paths[0])
    out = Scoped(trace.Trace())
    for plane in data.planes:
        device = plane.name.startswith(trace.DEVICE_PREFIX)
        if not device and not plane.name.startswith(trace.HOST_PREFIX):
            continue
        lines = out.trace.planes.setdefault(plane.name, {})
        if not device:
            for i, line in enumerate(plane.lines):
                key = line.name if line.name not in lines \
                    else f"{line.name} {i}"
                lines[key] = [(ev.name, float(ev.start_ns),
                               float(ev.duration_ns)) for ev in line.events]
            continue
        by_name = {l.name: l for l in plane.lines}
        modules = sorted((float(ev.start_ns), float(ev.end_ns),
                          _program(ev.name))
                         for ev in (by_name[MODULES_LINE].events
                                    if MODULES_LINE in by_name else ()))
        starts = [m[0] for m in modules]
        table = names.get(plane.name, {})
        evs, paths_ = [], []
        for ev in (by_name[trace.OPS_LINE].events
                   if trace.OPS_LINE in by_name else ()):
            start = float(ev.start_ns)
            k = bisect.bisect_right(starts, start) - 1
            program = modules[k][2] if k >= 0 and start < modules[k][1] \
                else ""
            evs.append((trace.short_name(ev.name), start,
                        float(ev.duration_ns)))
            paths_.append(table.get((program, ev.name), ""))
        lines[trace.OPS_LINE] = evs
        out.op_names[plane.name] = paths_
    return out


def scope_of(op_name: str) -> str:
    """The split an ``op_name`` counts toward: the first of :data:`SCOPES`
    among its scopes (``jit(chunk)/while/body/vmap(client)/transpose(jvp(
    segment))/conv`` holds ``client``), or "" (unscoped)."""
    tokens = set(re.split(r"[/():]", op_name))
    for token, key in SCOPES:
        if token in tokens:
            return key
    return ""


def device_split(scoped: Scoped, window: str) -> Dict[str, float]:
    """Seconds of device time inside the host span ``window``, averaged
    over the devices: ``busy`` (as ``trace.reduce`` has it), each of
    :data:`SPLITS` (the self time of the ops under it) and ``unscoped``,
    the rest of ``busy``."""
    _, w0, w1 = trace._find_window(scoped.trace, window)
    out = dict.fromkeys(SPLITS + ("unscoped", "busy"), 0.0)
    devices = [p for p, lines in scoped.trace.planes.items()
               if p.startswith(trace.DEVICE_PREFIX) and lines.get(
                   trace.OPS_LINE)]
    for plane in devices:
        evs = scoped.trace.planes[plane][trace.OPS_LINE]
        paths = scoped.op_names[plane]
        spans = []
        for i, start, end, own in trace.self_times(
                [(i, e[1], e[2]) for i, e in enumerate(evs)]):
            a, b = max(start, w0), min(end, w1)
            if b <= a:
                continue
            spans.append((a, b))
            key = scope_of(paths[i])
            if key:
                out[key] += own * (b - a) / (end - start) * 1e-9
        out["busy"] += sum(b - a for a, b in trace.union(spans)) * 1e-9
    n = max(1, len(devices))
    out = {k: v / n for k, v in out.items()}
    out["unscoped"] = out["busy"] - sum(out[k] for k in SPLITS)
    return out


def phase_gaps(scoped: Scoped, window: str) -> List[Tuple[str, float]]:
    """The device's idle gaps in ``window``, each named by the innermost
    program span (``repro.*``) on the window's thread over its middle, or
    by ``window`` where there is none: ``trace.reduce``'s gaps with the
    runtime's own spans left out."""
    keep = Scoped(trace.Trace({
        p: (lines if p.startswith(trace.DEVICE_PREFIX) else
            {l: [e for e in evs if e[0] == window
                 or e[0].startswith(PROGRAM)]
             for l, evs in lines.items()})
        for p, lines in scoped.trace.planes.items()}))
    return trace.reduce(keep.trace, window).gaps


def metrics(scoped: Scoped, window: str, trace_rounds: int,
            phases: Optional[Dict[str, Dict[str, float]]] = None,
            rounds: int = 0, window_s: float = 0.0) -> Dict[str, float]:
    """The per-layer numbers of a traced call of ``trace_rounds`` rounds,
    and, from ``phases`` (``engine.last_run_stats`` of an untraced call of
    ``rounds`` rounds that took ``window_s``), the program counters that it
    holds: fused engine staging and carry, or the reference engine's
    per-step dispatch and sync."""
    split = device_split(scoped, window)
    out = {f"device_ms.{k}": 1e3 * split[k] / trace_rounds for k in SPLITS}
    if split["busy"] > 0:
        out["device_unscoped_share"] = 100.0 * split["unscoped"] / split[
            "busy"]
    gaps = phase_gaps(scoped, window)
    idle = sum(s for _, s in gaps)
    if idle > 0:
        out["idle_unattributed_share"] = 100.0 * sum(
            s for label, s in gaps if label in (window, TRAIN)) / idle
    phases = phases or {}

    def s(name):
        return phases.get(name, {}).get("s", 0.0)
    if "repro.stage.fill" in phases and rounds:
        out["stage_fill_ms_per_round"] = 1e3 * s("repro.stage.fill") / rounds
        out["stage_put_ms_per_round"] = 1e3 * s("repro.stage.put") / rounds
    if "repro.fused.stack_carry" in phases and window_s > 0:
        out["carry_swap_share"] = 100.0 * (
            s("repro.fused.stack_carry") + s("repro.fused.unstack_carry")
        ) / window_s
    steps = phases.get("repro.ref.client_step", {}).get("n", 0)
    if steps:
        out["step_dispatch_ms"] = 1e3 * (
            s("repro.ref.client_step") + s("repro.ref.server_step")) / steps
        out["loss_sync_ms"] = 1e3 * s("repro.ref.loss_sync") / steps
    return out


def main(argv) -> int:
    if len(argv) != 3:
        print(__doc__.split("\n\n")[-2].strip(), file=sys.stderr)
        return 2
    log_dir, window, rounds = argv[0], argv[1], int(argv[2])
    scoped = load(log_dir)
    print(json.dumps({"split_s": device_split(scoped, window),
                      "metrics": metrics(scoped, window, rounds)}))
    by: Dict[str, float] = {}
    for label, s in phase_gaps(scoped, window):
        by[label] = by.get(label, 0.0) + s
    print("idle by program span: " + "  ".join(
        f"{k} {v:.6f}s" for k, v in sorted(by.items(), key=lambda kv: -kv[1])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
