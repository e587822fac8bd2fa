"""The program's own spans and model scopes, read against a trace's
device time.

The program marks its layers on the profiler's clock (``repro.obs``):

* host spans whose names start with ``sched.``, ``freeze.``, ``data.`` or
  ``host.``, plus the runtime's ``PJRT_Client_Compile`` span around every
  compile (:data:`PROGRAM_PREFIXES`, :data:`COMPILE_SPAN`);
* model scopes (``jax.named_scope``, the names in ``repro.obs.SCOPES``),
  which reach the compiled program's ``op_name`` metadata and never the
  trace itself: each traced instruction is mapped to its scope through the
  compiled program's text (:func:`scope_map`).

Two reductions read them, on a :class:`harness.trace.Trace`'s device
operations:

* :func:`idle_by_span` — the idle time of the first device inside a
  window, each idle stretch given, instant by instant, to the innermost
  program span open then (the one that started last), the rest to
  ``None``;
* :func:`scope_time` — device seconds per model scope, each operation
  mapped by the instruction map of the program that ran in its part of the
  window, with the seconds whose scope came from an instruction's own
  metadata kept apart from those whose scope was guessed from its
  neighbours (:class:`ScopeMap`); loops and calls, which only hold other
  operations, are left out as ``Trace.top_ops`` leaves them out.

All times are nanoseconds on the trace's clock, as in ``harness.trace``.
"""

from __future__ import annotations

import glob
import os
import re
import time
from typing import (Dict, Iterable, List, NamedTuple, Optional, Sequence,
                    Tuple)

from harness.trace import _CONTAINERS, Event, Interval, base_name

PROGRAM_PREFIXES = ("sched.", "freeze.", "data.", "host.")
COMPILE_SPAN = "PJRT_Client_Compile"


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES) or name == COMPILE_SPAN


# --------------------------------------------------------------------------
# reading the spans
# --------------------------------------------------------------------------

def program_spans(pd) -> List[Event]:
    """The program's host spans in a ``jax.profiler.ProfileData``, by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if is_program_span(e.name):
                    out.append(Event(e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)))
    out.sort(key=lambda e: e.start)
    return out


def program_spans_from_dir(root: str) -> List[Event]:
    """:func:`program_spans` of the newest ``*.xplane.pb`` under ``root``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return program_spans(ProfileData.from_file(paths[-1]))


def within(spans: Iterable[Event], window: Interval) -> List[Event]:
    """The spans that overlap ``window``."""
    return [e for e in spans if e.end > window[0] and e.start < window[1]]


# --------------------------------------------------------------------------
# idle time under the innermost span
# --------------------------------------------------------------------------

def innermost_segments(spans: Sequence[Event]
                       ) -> List[Tuple[float, float, str]]:
    """The timeline of ``spans`` cut into (start, end, innermost name)
    pieces: at each instant the open span that started last."""
    cuts = sorted({t for e in spans for t in (e.start, e.end)})
    if not cuts:
        return []
    by_start = sorted(spans, key=lambda e: e.start)
    out: List[Tuple[float, float, str]] = []
    open_: List[Event] = []
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(by_start) and by_start[k].start <= a:
            open_.append(by_start[k])
            k += 1
        open_ = [e for e in open_ if e.end > a]
        if open_:
            inner = max(open_, key=lambda e: e.start)
            out.append((a, b, inner.name))
    return out


def idle_gaps(trace, window: Interval) -> List[Interval]:
    """The stretches of ``window`` in which the first device ran nothing."""
    if not trace.ops:
        return [window]
    dev = sorted(trace.ops)[0]
    gaps, t = [], window[0]
    for a, b in trace.busy_intervals(dev, window):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < window[1]:
        gaps.append((t, window[1]))
    return gaps


def idle_by_span(trace, spans: Sequence[Event], window: Interval,
                 ) -> Dict[Optional[str], float]:
    """Idle seconds of the first device in ``window`` by the innermost
    program span open at each instant (``None``: no span open)."""
    segs = innermost_segments(within(spans, window))
    acc: Dict[Optional[str], float] = {}
    j = 0
    for a, b in idle_gaps(trace, window):
        covered = 0.0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, name = segs[k]
            d = min(b, s1) - max(a, s0)
            if d > 0:
                acc[name] = acc.get(name, 0.0) + d
                covered += d
            k += 1
        if b - a > covered:
            acc[None] = acc.get(None, 0.0) + (b - a - covered)
    return {k: v / 1e9 for k, v in acc.items()}


def by_prefix(split: Dict[Optional[str], float], prefix: str
              ) -> Dict[str, float]:
    """The entries of ``split`` whose span name starts with ``prefix``."""
    return {k: v for k, v in split.items()
            if k is not None and k.startswith(prefix)}


def durations(spans: Sequence[Event], name: str, window: Interval
              ) -> List[float]:
    """Seconds of each span called ``name`` that starts inside ``window``."""
    return [(e.end - e.start) / 1e9 for e in spans
            if e.name == name and window[0] <= e.start < window[1]]


# --------------------------------------------------------------------------
# device time per model scope
# --------------------------------------------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([A-Za-z0-9_.\-]+) \(.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply)=%?([A-Za-z0-9_.\-]+)")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?[A-Za-z0-9_.\-]+")
_OPERAND = re.compile(r"%([A-Za-z0-9_.\-]+)")
_WRAPPED = re.compile(r"^[A-Za-z_]+\((.*)\)$")


def scope_of(path: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of ``scopes`` in an ``op_name`` path, looking through
    ``jvp(...)``/``transpose(...)`` wrappers, so backward and rematerialized
    operations count with their forward scope."""
    found = None
    for part in path.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if part in scopes:
            found = part
    return found


#: how an instruction's scope was found (:class:`ScopeMap`); the first two
#: read the instruction's own metadata, the last guesses from neighbours
HOW = ("own", "called", "near")


class ScopeMap(NamedTuple):
    """Instruction name -> scope of one compiled program, kept apart by
    how the scope was found: ``own``, from the instruction's own
    ``op_name``; ``called``, for an instruction with none of its own that
    calls a computation (a fusion the compiler made, or whose metadata it
    dropped, or a reduction's combiner), from that computation's
    instructions (its root's scope, else its first scoped instruction's,
    through nested computations); ``near``, for the rest, from the first
    scoped instruction that uses it or that it uses, over up to four links
    of such instructions."""
    own: Dict[str, str]
    called: Dict[str, str]
    near: Dict[str, str]

    def lookup(self, name: str) -> Tuple[Optional[str], Optional[str]]:
        """(scope, one of :data:`HOW`), or (None, None)."""
        for how, m in zip(HOW, self):
            if name in m:
                return m[name], how
        return None, None


def scope_map(hlo_text: str, scopes: Sequence[str]) -> ScopeMap:
    """The :class:`ScopeMap` of a compiled program's ``as_text()``."""
    own: Dict[str, str] = {}
    comps: Dict[str, List[str]] = {}  # computation -> instructions, root first
    callees: Dict[str, List[str]] = {}  # unscoped instruction -> computations
    operands: Dict[str, List[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                current = c.group(1)
            continue
        name = m.group(1)
        if current is not None:
            names = comps.setdefault(current, [])
            if line.lstrip().startswith("ROOT"):
                names.insert(0, name)
            else:
                names.append(name)
        body = line.split(" = ", 1)[1].split(", metadata=", 1)[0]
        operands[name] = _OPERAND.findall(_CALLED.sub("", body))
        o = _OP_NAME.search(line)
        sc = scope_of(o.group(1), scopes) if o is not None else None
        if sc is not None:
            own[name] = sc
        elif _CALLS.search(body):
            callees[name] = _CALLS.findall(body)
    called: Dict[str, str] = {}

    def of_computation(comp):
        for x in comps.get(comp, ()):
            sc = own.get(x) or called.get(x)
            if sc is not None:
                return sc
        return None

    grew = True
    while grew:  # computations inside computations
        grew = False
        for name, cs in callees.items():
            if name not in called:
                sc = next(filter(None, map(of_computation, cs)), None)
                if sc is not None:
                    called[name] = sc
                    grew = True
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for op in ops:
            users.setdefault(op, []).append(name)
    near: Dict[str, str] = {}

    def scoped(name):
        return own.get(name) or called.get(name) or near.get(name)

    todo = [name for name in operands if scoped(name) is None]
    for _ in range(4):  # chains of unscoped instructions
        grew = False
        for name in todo:
            if scoped(name) is not None:
                continue
            hits = [u for u in users.get(name, ()) if scoped(u)] or \
                   [x for x in operands[name] if scoped(x)]
            if hits:
                near[name] = scoped(hits[0])
                grew = True
        if not grew:
            break
    return ScopeMap(own, called, near)


#: a :func:`scope_time` key: (scope, one of :data:`HOW`, operation); the
#: first two are None for an operation no map names
ScopeKey = Tuple[Optional[str], Optional[str], str]


def scope_time(trace, parts: Sequence[Tuple[Interval, ScopeMap]]
               ) -> Dict[ScopeKey, float]:
    """Device seconds (averaged over devices) by scope, by how the scope
    was found and by base operation name, each part of the window read
    with its own program's map."""
    acc: Dict[ScopeKey, float] = {}
    n = max(len(trace.ops), 1)
    for evs in trace.ops.values():
        for window, smap in parts:
            for e in evs:
                op = base_name(e.name)
                if op in _CONTAINERS:
                    continue
                t = min(e.end, window[1]) - max(e.start, window[0])
                if t <= 0:
                    continue
                k = smap.lookup(e.name) + (op,)
                acc[k] = acc.get(k, 0.0) + t
    return {k: v / n / 1e9 for k, v in acc.items()}


def sum_by(table: Dict[tuple, float], *fields: int) -> Dict:
    """``table`` summed over every key field but ``fields`` (positions in
    the key); one field gives plain keys, more give tuples."""
    out: Dict = {}
    for k, v in table.items():
        key = k[fields[0]] if len(fields) == 1 else tuple(k[i] for i in fields)
        out[key] = out.get(key, 0.0) + v
    return out


def halves(trace, window: Interval, swap: str = "bench.swap"
           ) -> Optional[Tuple[Interval, Interval]]:
    """A training window's two phases: before and after its swap span."""
    sw = trace.span_window(swap)
    if sw is None or not (window[0] <= sw[0] <= window[1]):
        return None
    return (window[0], sw[0]), (sw[1], window[1])


def train_scope_maps(ctx) -> Optional[Dict[int, ScopeMap]]:
    """The :class:`ScopeMap` of each of a training cell's two phase
    programs, compiled again from their shapes exactly as the train job
    compiles them; the compiler names instructions the same way every time.
    The persistent compile cache keys these compiles by their metadata too
    (without, it may hand back an executable another checkout of the same
    program compiled, whose metadata carries no scopes) and keeps none of
    them, so the timed programs' entries are never pushed out of a cache of
    bounded size: every traced run compiles them cold.  Kept on ``ctx``
    (``ctx.scope_maps``, and the seconds they took in
    ``ctx.scope_maps_s``), so the readers of one run compile once.  None
    when the program opens no model scopes."""
    import jax
    import repro.obs
    scopes = getattr(repro.obs, "SCOPES", None)
    if not scopes:
        return None
    if getattr(ctx, "scope_maps", None) is not None:
        return ctx.scope_maps
    from harness import program
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh
    t0 = time.perf_counter()
    run = program.train_run(ctx.config, ctx.traffic, interpret=ctx.interpret,
                            smoke_model=ctx.smoke_model)
    mesh = make_host_mesh(1, 1)
    train_step = steps.build_train_step(run, mesh)
    batch = steps.batch_specs(run, mesh)
    flags = {"jax_compilation_cache_include_metadata_in_key": True,
             "jax_persistent_cache_min_compile_time_secs": float("inf")}
    was = {k: getattr(jax.config, k) for k in flags}
    try:
        for k, v in flags.items():
            jax.config.update(k, v)
        maps = {}
        for phase in (0, 1):
            compiled = steps.compile_train_step(
                run, mesh, train_step, steps.abstract_state(run, mesh, phase),
                batch, phase)
            maps[phase] = scope_map(compiled.as_text(), scopes)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
    ctx.scope_maps, ctx.scope_maps_s = maps, time.perf_counter() - t0
    return maps
