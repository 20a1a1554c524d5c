"""Device time by named scope: a compiled program's HLO text says which
`jax.named_scope` each instruction was traced under, and a reduced trace
(`trace_reduce.reduce`) says how long each instruction ran.

An instruction's scope is its `op_name` metadata, a path such as

    jit(train_step)/transpose(jvp(lram))/gather/jit(_take)/scatter-add

whose transformation wrappers (`jvp(...)`, `transpose(...)`, `vmap(...)`,
`remat(...)`) are unwrapped into plain segments, here `jit(train_step)
/lram/gather/jit(_take)/scatter-add`: a backward pass is found under the
scope of its forward.  A path lies under a scope when the scope's segments
appear in it in a row (`lram/gather` above).

The instructions that run as device operations are those of the entry
computation and of the computations it calls as operations (while bodies
and conditions, conditional branches, calls), recursively; a fusion runs as
one operation, named by the fusion instruction, and XLA gives it the
metadata of its root.  So a fusion of ops from two scopes is counted in
its root's scope.

On a TPU a device event is named by its HLO instruction's text (`%fusion.5 =
f32[...] fusion(...)`); the instruction name before ` = ` is looked up in
the program's map.  The traced window must run that program alone, as the
train cell's does: two programs can share an instruction name.

The program's text comes from the profile itself: `jax.profiler` keeps
each program it saw run as an `HloProto` in the profile's `/host:metadata`
plane, and the program that ran longest on the devices (the `XLA Modules`
lines) is the window's.  That is the executable the window ran, compiled
or read from the compile cache, and nothing is compiled to read it.
"""

from __future__ import annotations

import functools
import glob
import re

WRAPPERS = ("jvp", "transpose", "vmap", "remat")

_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_CALLED = re.compile(
    r"\b(body|condition|true_computation|false_computation|to_apply|calls)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_CALLERS = {"while", "conditional", "call"}

METADATA_PLANE = b"/host:metadata"
HLO_PROTO_STAT = b"Hlo Proto"
MODULES_LINE = "XLA Modules"


def split_path(path: str) -> list[str]:
    """The segments of an `op_name` path, each transformation wrapper
    replaced by the segments of what it wraps (`jvp()` by none)."""
    out, depth, start = [], 0, 0
    pieces = []
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            pieces.append(path[start:i])
            start = i + 1
    pieces.append(path[start:])
    for seg in pieces:
        head, _, rest = seg.partition("(")
        if head in WRAPPERS and rest.endswith(")"):
            out.extend(split_path(rest[:-1]))
        elif seg:
            out.append(seg)
    return out


def under(segments: list[str], scope: str) -> bool:
    """Whether `scope` (`a/b`) appears in `segments` as a run."""
    want = scope.split("/")
    n = len(want)
    return any(segments[i:i + n] == want
               for i in range(len(segments) - n + 1))


def _computations(hlo: str) -> tuple[dict[str, list[str]], str | None]:
    """{computation name: its instruction lines}, and the entry's name."""
    comps: dict[str, list[str]] = {}
    entry, cur = None, None
    for line in hlo.splitlines():
        if cur is None:
            m = _COMP.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
        elif line.strip() == "}":
            cur = None
        else:
            comps[cur].append(line)
    return comps, entry


def _called(rhs: str) -> list[str]:
    """Computations an instruction runs as operations (not fused or
    applied per element)."""
    m = _OPCODE.search(" " + rhs.split(", metadata=", 1)[0])
    if not m or m.group(1) not in _CALLERS:
        return []
    names = [c for k, c in _CALLED.findall(rhs)
             if k != "calls" or m.group(1) == "call"]
    for group in _BRANCHES.findall(rhs):
        names += [g.strip().lstrip("%") for g in group.split(",")]
    return names


def device_computations(hlo: str) -> list[str]:
    """The computations whose instructions run as device operations: the
    entry first, then those it calls as operations, recursively."""
    return _device_computations(*_computations(hlo))


def _device_computations(comps, entry) -> list[str]:
    order, todo = [], [entry] if entry else []
    while todo:
        name = todo.pop()
        if name in order or name not in comps:
            continue
        order.append(name)
        for line in comps[name]:
            m = _INSTR.match(line)
            if m:
                todo.extend(_called(m.group(2)))
    return order


@functools.lru_cache(maxsize=1)
def op_scopes(hlo: str) -> dict[str, dict]:
    """{instruction name: {'path': op_name, 'segments': unwrapped path,
    'entry': whether it is in the entry computation}} for every
    instruction that runs as a device operation; instructions with no
    `op_name` are left out."""
    comps, entry = _computations(hlo)
    out = {}
    for name in _device_computations(comps, entry):
        for line in comps[name]:
            m = _INSTR.match(line)
            op = _OP_NAME.search(line)
            if m and op:
                path = op.group(1).replace('\\"', '"')
                out[m.group(1)] = {"path": path,
                                   "segments": split_path(path),
                                   "entry": name == entry}
    return out


def instruction_name(event_name: str) -> str:
    """The HLO instruction a TPU device event names."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


# ------------------------------------------------- the program of a profile

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        value |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf: bytes):
    """(field number, wire type, field start, value start, end) of each
    field of one protobuf message, read from its wire format; a
    length-delimited value starts after its length, a varint's value is
    at its value start."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, (_, i) = i, _varint(buf, i)
        elif wire == 2:
            n, value = _varint(buf, i)
            i = value + n
        elif wire in (1, 5):
            value, i = i, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unexpected wire type {wire}")
        yield key >> 3, wire, start, value, i


def _sub(buf: bytes, number: int) -> list[bytes]:
    """The length-delimited values of field `number` of a message."""
    return [buf[v:e] for f, w, _, v, e in fields(buf)
            if f == number and w == 2]


def _map_values(buf: bytes, number: int) -> list[bytes]:
    """The values of map field `number` (entries: key 1, value 2)."""
    return [v for entry in _sub(buf, number) for v in _sub(entry, 2)]


def _varint_field(buf: bytes, number: int) -> int | None:
    for f, w, _, v, _ in fields(buf):
        if f == number and w == 0:
            return _varint(buf, v)[0]
    return None


def hlo_protos(xspace: bytes) -> dict[str, bytes]:
    """{program name as the profile's `XLA Modules` lines name it
    (`jit_train_step(<id>)`): its serialized `HloModuleProto`} from the
    `/host:metadata` plane of a serialized `XSpace` (planes: field 1).
    There each program is an `XEventMetadata` (the plane's map field 4;
    name 2, stats 5) with an `XStat` (metadata id 1, bytes 6) whose
    `XStatMetadata` (the plane's map field 5; id 1, name 2) is named
    `Hlo Proto`; the module is the `HloProto`'s field 1."""
    for plane in _sub(xspace, 1):
        if _sub(plane, 2) != [METADATA_PLANE]:
            continue
        ids = {_varint_field(m, 1) for m in _map_values(plane, 5)
               if _sub(m, 2) == [HLO_PROTO_STAT]}
        out = {}
        for event in _map_values(plane, 4):
            name = _sub(event, 2)
            for stat in _sub(event, 5):
                proto = _sub(stat, 6)
                if name and proto and _varint_field(stat, 1) in ids:
                    module = _sub(proto[0], 1)
                    if module:
                        out[name[0].decode()] = module[0]
        return out
    return {}


def module_text(module_proto: bytes) -> str:
    """An `HloModuleProto` as HLO text with its metadata, as
    `compiled.as_text()` prints it."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        module_proto).to_string(opts)


def longest_program(profile) -> str | None:
    """The name of the program with the most device time on the `XLA
    Modules` lines of a `jax.profiler.ProfileData`, None without such
    lines."""
    time: dict[str, float] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    time[ev.name] = time.get(ev.name, 0.0) + ev.duration_ns
    return max(time, key=time.get) if time else None


@functools.lru_cache(maxsize=2)
def profile_hlo(path: str) -> str | None:
    """The HLO text, with its metadata, of the program that ran longest in
    the profile at `path`; None where the profile names no program or
    holds no `HloProto` of it (the metadata plane names each program as
    the `XLA Modules` lines do)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    name = longest_program(ProfileData.from_serialized_xspace(raw))
    proto = hlo_protos(raw).get(name) if name else None
    return module_text(proto) if proto else None


def program_hlo(ctx) -> str | None:
    """The HLO text of the program the traced window ran: `ctx['hlo']`
    where the caller gives it, else that of the profile the run is
    reading, which `harness.trace_dir()` of this process holds until the
    per-layer metrics are read."""
    if "hlo" in ctx:
        return ctx["hlo"]
    import harness

    found = sorted(glob.glob(str(harness.trace_dir() / "**" / "*.xplane.pb"),
                             recursive=True))
    return profile_hlo(found[-1]) if found else None


# ------------------------------------------------------------ time by scope

def scope_seconds(ctx, scope: str) -> tuple[float, int] | None:
    """(device seconds under `scope` over the traced window, the program's
    executions in it) for a reader's `ctx`, or None where there is no
    trace, no program text (`program_hlo`), or no op matches.  The
    executions are the largest `count` among the matched entry-computation
    ops (a while body's ops run more often than the program)."""
    trace = ctx.get("trace")
    if not trace:
        return None
    hlo = program_hlo(ctx)
    if not hlo:
        return None
    known = op_scopes(hlo)
    seconds, counts, entry_counts = 0.0, [], []
    for name, v in trace.get("ops", {}).items():
        info = known.get(instruction_name(name))
        if info is not None and under(info["segments"], scope):
            seconds += v["s"]
            counts.append(v["count"])
            if info["entry"]:
                entry_counts.append(v["count"])
    if not counts or seconds <= 0:
        return None
    return seconds, max(entry_counts or counts)


def scope_ms_per_step(ctx, scope: str) -> float | None:
    got = scope_seconds(ctx, scope)
    if got is None:
        return None
    seconds, steps = got
    return 1e3 * seconds / steps
