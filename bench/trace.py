"""Reduction of a JAX profiler trace to the numbers the metrics read.

The window runs under ``jax.profiler``; this reads the ``.xplane.pb`` it
writes.  Device operations are the events of the ``XLA Ops`` line of each
TPU plane; the scope of an operation is the ``jax.named_scope`` path the
program put on it, which reaches the trace as the op's ``tf_op`` stat.
Host spans are the events of the host plane (the ``TraceAnnotation``
spans among them), on the same clock.

The window is the host span ``bench.window`` that the harness puts around
the cell's window.  Busy time is the union of the intervals in which an
operation ran on a device, averaged over the devices used.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Op:
    start: int      # ns
    end: int        # ns
    name: str
    text: str       # name, op path and metadata, for scope matching
    device: int


@dataclasses.dataclass
class Span:
    start: int
    end: int
    name: str


def _scope_re(scope: str):
    return re.compile(r"(^|[/\s])" + re.escape(scope) + r"(/|\s|$|\()")


def union_ns(intervals) -> int:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclasses.dataclass
class Summary:
    ops: list[Op]
    spans: list[Span]
    start: int
    end: int
    num_devices: int

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    def _clip(self, a: int, b: int):
        return max(a, self.start), min(b, self.end)

    def device_intervals(self, device: int):
        out = []
        for op in self.ops:
            if op.device == device:
                s, e = self._clip(op.start, op.end)
                if e > s:
                    out.append((s, e))
        return out

    @property
    def busy_s(self) -> float:
        per = [union_ns(self.device_intervals(d))
               for d in range(self.num_devices)]
        return sum(per) / len(per) / 1e9

    def scope_s(self, scope: str) -> float:
        """Device seconds of the window's operations under ``scope``,
        summed over devices and averaged per device."""
        pat = _scope_re(scope)
        total = 0
        for op in self.ops:
            if pat.search(op.text):
                s, e = self._clip(op.start, op.end)
                total += max(e - s, 0)
        return total / self.num_devices / 1e9

    def top_ops(self, n: int = TOP):
        by: dict[str, int] = {}
        for op in self.ops:
            s, e = self._clip(op.start, op.end)
            if e > s:
                by[op.name] = by.get(op.name, 0) + e - s
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / self.num_devices] for k, v in top]

    def idle_gaps(self, n: int = TOP):
        """The longest gaps of device 0 in the window, each named by the
        host span that overlaps it most."""
        iv = sorted(self.device_intervals(0))
        gaps, cur = [], self.start
        for s, e in iv:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if self.end > cur:
            gaps.append((cur, self.end))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            best, label = 0, "no host span"
            for sp in self.spans:
                if sp.name == WINDOW_SPAN:
                    continue
                ov = min(b, sp.end) - max(a, sp.start)
                if ov > best:
                    best, label = ov, sp.name
            out.append([label, (b - a) / 1e9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _device_index(plane_name: str):
    m = re.match(r"/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


# --- the XSpace protobuf, read directly -------------------------------
# ``jax.profiler.ProfileData`` gives each event's own stats but not its
# metadata's, and the op path (``tf_op``, from the HLO op_name that
# ``jax.named_scope`` sets) lives in the metadata.  The wire format is
# small enough to read here: XSpace.planes=1; XPlane name=2 lines=3
# event_metadata=4 stat_metadata=5; XLine name=2 timestamp_ns=3 events=4;
# XEvent metadata_id=1 offset_ps=2 duration_ps=3 stats=4; XEventMetadata
# id=1 name=2 stats=5; XStatMetadata id=1 name=2; XStat metadata_id=1,
# str_value=5, ref_value=7 (a stat metadata id whose name is the value).

def _varint(b: bytes, i: int):
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        s += 7
        if x < 0x80:
            return r, i


def _fields(b: bytes):
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wt == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wt == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wt}")
        yield f, v


def _map_entry(b: bytes):
    k = v = None
    for f, x in _fields(b):
        if f == 1:
            k = x
        elif f == 2:
            v = x
    return k, v


def _str_stats(raw: list, stat_names: dict) -> dict:
    out = {}
    for b in raw:
        sid = val = None
        for f, x in _fields(b):
            if f == 1:
                sid = x
            elif f == 5:
                val = x.decode(errors="replace")
            elif f == 7:
                val = stat_names.get(x)
        if sid in stat_names and isinstance(val, str):
            out[stat_names[sid]] = val
    return out


def _planes(data: bytes):
    """(name, lines, event names, event op paths) of each plane; a line is
    (name, [(start_ns, end_ns, metadata_id)])."""
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, lines, emeta, snames = "", [], {}, {}
        for pf, x in _fields(plane):
            if pf == 2:
                name = x.decode(errors="replace")
            elif pf == 3:
                lines.append(x)
            elif pf == 4:
                k, v = _map_entry(x)
                emeta[k] = v
            elif pf == 5:
                k, v = _map_entry(x)
                for sf, y in _fields(v or b""):
                    if sf == 2:
                        snames[k] = y.decode(errors="replace")
        names, paths = {}, {}
        for k, v in emeta.items():
            raw = []
            for mf, y in _fields(v or b""):
                if mf == 2:
                    names[k] = y.decode(errors="replace")
                elif mf == 5:
                    raw.append(y)
            paths[k] = _str_stats(raw, snames).get("tf_op", "")
        out_lines = []
        for ln in lines:
            lname, ts, events = "", 0, []
            for lf, y in _fields(ln):
                if lf == 2:
                    lname = y.decode(errors="replace")
                elif lf == 3:
                    ts = y
                elif lf == 4:
                    mid = off = dur = 0
                    for ef, z in _fields(y):
                        if ef == 1:
                            mid = z
                        elif ef == 2:
                            off = z
                        elif ef == 3:
                            dur = z
                    s = ts + off // 1000
                    events.append((s, s + dur // 1000, mid))
            out_lines.append((lname, events))
        yield name, out_lines, names, paths


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def _operands(rhs: str) -> list[str]:
    """The %names inside the operand parentheses of an instruction."""
    i = rhs.find("(", rhs.find(" ") + 1 if not rhs.startswith("(") else
                 _close(rhs, 0) + 1)
    if i < 0:
        return []
    return _REF.findall(rhs[i:_close(rhs, i) + 1])


def _close(text: str, i: int) -> int:
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            return j
    return len(text) - 1


def hlo_paths(texts) -> dict[str, str]:
    """Op path of every instruction of the compiled HLO ``texts``: its own
    ``op_name``, else that of the ops that consume it, else that of its
    operands.  XLA gives no op_name to some ops it makes, such as the sort
    and fusion of an expanded scatter; they serve their consumers, whose
    scope they belong to."""
    own, args = {}, {}
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                name, rhs = m.groups()
                op = _OP_NAME.search(rhs)
                own[name] = op.group(1) if op else ""
                args[name] = _operands(rhs)
    users: dict[str, list[str]] = {}
    for name, ops in args.items():
        for a in ops:
            users.setdefault(a, []).append(name)
    memo: dict[str, str] = {}

    def walk(name: str, edges: dict) -> str:
        seen, todo = {name}, list(edges.get(name, ()))
        while todo:
            n = todo.pop(0)
            if own.get(n):
                return own[n]
            for m in edges.get(n, ()):
                if m not in seen:
                    seen.add(m)
                    todo.append(m)
        return ""

    for name in own:
        memo[name] = own[name] or walk(name, users) or walk(name, args)
    return memo


def short_name(hlo_text: str) -> str:
    """``%fusion.1 = s32[...] fusion(...)`` -> ``fusion.1``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def from_xspace(data: bytes, device_ids, hlo_texts=()) -> Summary:
    """Reduce a serialized XSpace to the ops of ``device_ids``' planes and
    the host spans.  An op that carries no op path takes the one
    ``hlo_paths`` finds for its name in ``hlo_texts`` (the compiled
    programs of the window), else stays outside every scope."""
    index = {d: i for i, d in enumerate(device_ids)}
    from_hlo = hlo_paths(hlo_texts)
    ops, spans = [], []
    for name, lines, names, paths in _planes(data):
        dev = _device_index(name)
        if dev is not None:
            if dev not in index:
                continue
            for ln, evs in lines:
                if ln != OPS_LINE:
                    continue
                for s, e, mid in evs:
                    op = short_name(names.get(mid, "?"))
                    path = paths.get(mid, "") or from_hlo.get(op, "")
                    ops.append(Op(s, e, op, f"{op} {path}", index[dev]))
        elif name.startswith("/host:"):
            for _, evs in lines:
                spans += [Span(s, e, names.get(mid, "?"))
                          for s, e, mid in evs]
    win = [sp for sp in spans if sp.name == WINDOW_SPAN]
    if win:
        start, end = win[0].start, win[0].end
    elif ops:
        start, end = min(o.start for o in ops), max(o.end for o in ops)
    else:
        start = end = 0
    return Summary(ops, spans, start, end, len(device_ids))


def profile_options():
    """Profiler options of a traced window: no Python tracer, whose frame
    events would swamp the host spans that name the idle gaps."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def reduce(trace_dir: str, devs, hlo_texts=()) -> Summary:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return from_xspace(Path(files[-1]).read_bytes(), [d.id for d in devs],
                       hlo_texts)
