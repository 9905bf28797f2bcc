"""Reduction of a profiler trace to device busy time, op time, idle gaps
and exposed collectives.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU each
chip is a plane ``/device:TPU:<i>``.  Its ``XLA Ops`` line holds one event
per HLO op the core executes (a scan body's ops once per body, and a
``while`` or ``conditional`` once around the ops it contains), named by
the op's HLO text (``%fusion.3 = f32[...] fusion(...)``); its ``Async XLA
Ops`` line holds the in-flight spans of asynchronous copies and
collectives.  The host's spans are on the ``python*`` line of
``/host:CPU``.  A CPU profile has no device plane: there the XLA ops run
on the host's ``tf_XLA*`` threads.  Only where the caller asks for it
(``cpu_ok=True``, for a trace recorded without a chip) do all of them
together stand for one device; otherwise a trace without a TPU plane is
an error, never host threads read as the device.

Everything below :func:`load` works on plain ``(name, start_ns, end_ns)``
tuples, so the interval arithmetic is tested on synthetic intervals too.
"""
from __future__ import annotations

import dataclasses
import functools

#: collectives of the reduction (the paper's global dot products) and of
#: the halo exchange, by HLO opcode (``-start`` / ``-done`` forms included)
REDUCTION_OPS = ("all-reduce", "reduce-scatter", "all-gather", "psum")
HALO_OPS = ("collective-permute", "ppermute")
#: control-flow ops whose event spans the ops they contain: not work
CONTAINER_OPS = ("while", "conditional", "call")
#: bookkeeping events of the CPU runtime's threads, not ops
_CPU_SKIP = ("ThreadpoolListener", "ThunkExecutor", "end: ")


@dataclasses.dataclass
class Trace:
    """Per device: the core's ops and the asynchronous ops in flight; the
    host's main-thread events.  Nanoseconds on one clock."""
    devices: dict       # device name -> [(op name, start_ns, end_ns)]
    host: list          # [(event name, start_ns, end_ns)]
    async_ops: dict = dataclasses.field(default_factory=dict)


@functools.lru_cache(maxsize=4096)
def parse_op(text: str) -> tuple:
    """``(name, opcode)`` of an op event: from HLO text
    ``%name = <type> opcode(...)``, or the bare name itself."""
    if " = " not in text:
        return text, text.split(".")[0]
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):                 # a tuple type: skip its parens
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else rest
    return name.lstrip("%"), rest.lstrip().split("(", 1)[0]


@functools.lru_cache(maxsize=4096)
def kind_of(text: str) -> str:
    """``"reduction"``, ``"halo"``, ``"container"`` or ``"compute"``."""
    opcode = parse_op(text)[1].lower()
    if any(k in opcode for k in REDUCTION_OPS):
        return "reduction"
    if any(k in opcode for k in HALO_OPS):
        return "halo"
    if opcode in CONTAINER_OPS:
        return "container"
    return "compute"


def _events(line, names: dict) -> list:
    """``(name, start_ns, end_ns)`` of a line's events; ``names`` keeps one
    copy of each distinct name (an HLO op's text is long, and a trace
    holds it once per executed body)."""
    out = []
    for e in line.events:
        n = e.name
        out.append((names.setdefault(n, n), e.start_ns,
                    e.start_ns + e.duration_ns))
    return out


def load(path: str, cpu_ok: bool = False) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace`.  Raises ``ValueError``
    when it holds no ``/device:TPU:*`` plane, unless ``cpu_ok``, where the
    host's XLA threads stand for one device."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, async_ops, host, cpu_ops, names = {}, {}, [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = _events(line, names)
                elif line.name == "Async XLA Ops":
                    async_ops[plane.name] = _events(line, names)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host += _events(line, names)
                elif line.name.startswith("tf_XLA"):
                    cpu_ops += [ev for ev in _events(line, names)
                                if not ev[0].startswith(_CPU_SKIP)]
    if not devices:
        if not (cpu_ok and cpu_ops):
            raise ValueError(f"{path}: no /device:TPU:* plane in the trace")
        devices["/host:CPU"] = cpu_ops
    return Trace(devices=devices, host=host, async_ops=async_ops)


# ---- interval arithmetic -------------------------------------------------

def union(intervals) -> list:
    """Merge ``(start, end)`` pairs into sorted disjoint intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged) -> float:
    return float(sum(e - s for s, e in merged))


def clip(merged, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` that no interval of
    the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo, hi) -> list:
    """The idle intervals of ``[lo, hi]`` between busy intervals."""
    out, cur = [], lo
    for s, e in clip(merged, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


# ---- per-device reduction ------------------------------------------------

def busy(ops, lo, hi) -> list:
    """Union of the device's work intervals inside ``[lo, hi]``."""
    return clip(union((s, e) for n, s, e in ops
                      if kind_of(n) != "container"), lo, hi)


def exposed(ops, kind: str, lo, hi, async_ops=()) -> float:
    """Nanoseconds in ``[lo, hi]`` during which an op of ``kind``
    (``"reduction"`` or ``"halo"``) runs, on the core or in flight, and no
    compute op does."""
    mine = clip(union((s, e) for n, s, e in list(ops) + list(async_ops)
                      if kind_of(n) == kind), lo, hi)
    compute = union((s, e) for n, s, e in ops if kind_of(n) == "compute")
    return measure(subtract(mine, compute))


def op_seconds(ops, lo, hi) -> dict:
    """Device seconds per op name inside ``[lo, hi]``."""
    tot = {}
    for n, s, e in ops:
        if kind_of(n) == "container":
            continue
        s, e = max(s, lo), min(e, hi)
        if e > s:
            name = parse_op(n)[0]
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return tot


def span_extent(host, names) -> tuple | None:
    """``(first start, last end)`` of the host spans called ``names``."""
    spans = [(s, e) for n, s, e in host if n in names]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def host_activity(host, t) -> str:
    """Name of the innermost host event of the main thread at ``t``."""
    best = None
    for n, s, e in host:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "host idle"


def summarize(trace: Trace, window_spans, top: int = 10) -> dict | None:
    """Busy, idle and exposed-collective seconds per device over the
    extent of the host spans ``window_spans``; ``None`` when the trace
    holds no such span or no device op."""
    ext = span_extent(trace.host, window_spans)
    if ext is None or not any(trace.devices.values()):
        return None
    lo, hi = ext
    per_dev, ops_total, idle = {}, {}, []
    for dev, ops in sorted(trace.devices.items()):
        b = busy(ops, lo, hi)
        flying = trace.async_ops.get(dev, ())
        per_dev[dev] = {
            "busy_s": measure(b) * 1e-9,
            "reduction_exposed_s": exposed(ops, "reduction", lo, hi,
                                           flying) * 1e-9,
            "halo_exposed_s": exposed(ops, "halo", lo, hi, flying) * 1e-9,
        }
        for n, sec in op_seconds(ops, lo, hi).items():
            ops_total[n] = ops_total.get(n, 0.0) + sec
        if not idle:        # the first device's gaps stand for the host's
            longest = sorted(gaps(b, lo, hi), key=lambda g: g[0] - g[1])
            idle = [[host_activity(trace.host, (s + e) / 2), (e - s) * 1e-9]
                    for s, e in longest[:top]]
    n = len(per_dev)
    mean = {k: sum(d[k] for d in per_dev.values()) / n
            for k in ("busy_s", "reduction_exposed_s", "halo_exposed_s")}
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": n,
        **mean,
        "device_ops": sorted(([k, v / n] for k, v in ops_total.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle,
    }
