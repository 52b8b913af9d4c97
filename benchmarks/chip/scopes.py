"""Per-layer time from the program's own names: device time by named
scope, idle time by host span, from one traced sample of the cell's work.

The program names its device work with `jax.named_scope` (the fused
oracle step: 'matvec', 'counts', 'rmatvec'; the bundle step:
'plane_insert', 'qp'; the tree counting inside 'counts': 'sort', 'tree',
'query', 'unsort') and its host work with `jax.profiler.TraceAnnotation`
spans ('ranksvm.*', 'bmrm.*', 'oracle.*').

run.py reduces the window's trace and deletes it before any reader runs,
so the readers of these metrics share a sample of their own: on the
first call in a traced run, `sample(ctx)` copies the job, runs the copy's
window once to compile, then for SAMPLE_SECONDS under the profiler inside
a SAMPLE_SPAN span (`take`), reduces that trace, logs it, and keeps the
result on `ctx` for the other readers. The job the check reads
afterwards is left as its window left it.

The reduction:

- Device time by scope. Each device operation's self time (its time less
  that of the operations nested in it, as devtrace.self_times) goes to
  the innermost scope named among the components of its `op_name`
  metadata, the last component (the primitive) left out. The profiler
  records each compiled module's optimized HLO in its metadata plane; an
  operation is found there by its module (the 'XLA Modules' event it ran
  in) and its instruction name. Time of no scope is `unscoped`.
- Idle time by span. Each interval of the sample in which no operation
  ran on the device is split by the innermost program span open over it;
  JAX's own host events are skipped, and time under none reads NO_SPAN.

Every number is in ms per unit of the cell's work (per iteration or per
model, the traffic's `attempted` count of the sample).
"""

from __future__ import annotations

import collections
import copy
import dataclasses
import gc
import os
import shutil
import sys

import devtrace
import spec

SAMPLE_SPAN = 'bench.sample'
SAMPLE_SECONDS = 5.0
# Innermost wins; the counting sub-scopes lie inside 'counts'.
SCOPES = ('matvec', 'counts', 'rmatvec', 'plane_insert', 'qp',
          'sort', 'tree', 'query', 'unsort')
SPAN_PREFIXES = ('ranksvm.', 'bmrm.', 'oracle.')
UNSCOPED = 'unscoped'
NO_SPAN = 'no span'
HLO_PROTO_STAT = 'Hlo Proto'
MODULE_LINE = 'XLA Modules'


@dataclasses.dataclass
class Op:
    start: float       # seconds
    end: float
    module: str | None  # the module the operation ran in
    name: str          # its HLO instruction name


@dataclasses.dataclass
class Sample:
    devices: dict      # plane name -> [Op]
    host: list         # [devtrace.Event] on the thread holding SAMPLE_SPAN
    op_names: dict     # module -> {instruction name: op_name metadata}


# ------------------------------------------------------------ wire format


def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, bytes for a length-delimited field; fixed-width fields
    are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f'unsupported protobuf wire type {wire}')
        yield field, value


def _text(buf: bytes) -> str:
    return buf.decode('utf-8', 'replace')


def _instruction_op_names(hlo_proto: bytes) -> dict:
    """{instruction name: op_name} of one xla.HloProto (hlo_module = 1;
    HloModuleProto.computations = 3; HloComputationProto.instructions = 2;
    HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, inst in _fields(comp):
                if h != 2:
                    continue
                name, op_name = None, ''
                for k, v in _fields(inst):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = next((_text(x) for j, x in _fields(v)
                                        if j == 2), '')
                if name is not None:
                    out[name] = op_name
    return out


def hlo_op_names(xspace: bytes) -> dict:
    """{module name: {instruction: op_name}} from the HLO protos the
    profiler keeps in the metadata plane of a serialized XSpace
    (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; map entries key = 1, value = 2; XEventMetadata
    .name = 2, .stats = 5; XStat.metadata_id = 1, .bytes_value = 6;
    XStatMetadata.name = 2)."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and _text(v) == '/host:metadata'
                   for k, v in fields):
            continue
        stat_ids = set()
        for k, entry in fields:
            if k == 5:
                e = dict(_fields(entry))
                if any(j == 2 and _text(x) == HLO_PROTO_STAT
                       for j, x in _fields(e.get(2, b''))):
                    stat_ids.add(e.get(1))
        for k, entry in fields:
            if k != 4:
                continue
            meta = dict(_fields(entry)).get(2, b'')
            name, protos = None, []
            for j, x in _fields(meta):
                if j == 2:
                    name = _text(x)
                elif j == 5:
                    stat = dict(_fields(x))
                    if stat.get(1) in stat_ids and 6 in stat:
                        protos.append(stat[6])
            if name is not None and protos:
                out[name] = _instruction_op_names(protos[0])
    return out


# ------------------------------------------------------------------ load


def _instruction(event_name: str) -> str:
    """'%fusion.12 = f32[8]{0} fusion(...)' -> 'fusion.12'."""
    return event_name.partition(' = ')[0].split(' ', 1)[0].lstrip('%')


def load(path: str) -> Sample:
    """Device operations with their modules, the host spans of the thread
    that ran the sample, and the HLO op names of one `.xplane.pb`."""
    from jax.profiler import ProfileData
    with open(path, 'rb') as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith('/device:'):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                             for e in lines.get(MODULE_LINE, ()))
            ops = sorted((e.start_ns * 1e-9, e.end_ns * 1e-9,
                          _instruction(e.name))
                         for name in devtrace.DEVICE_OP_LINES
                         for e in lines.get(name, ()))
            out, k = [], 0
            for s, e, name in ops:
                while k < len(modules) and modules[k][1] < s:
                    k += 1
                inside = k < len(modules) and modules[k][0] <= s
                out.append(Op(s, e, modules[k][2] if inside else None,
                              name))
            if out:
                devices[plane.name] = out
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                evs = [devtrace.Event(e.start_ns * 1e-9, e.end_ns * 1e-9,
                                      e.name)
                       for e in line.events if e.duration_ns > 0]
                if any(e.name == SAMPLE_SPAN for e in evs):
                    host = evs
    return Sample(devices, host, hlo_op_names(raw))


# ---------------------------------------------------------------- reduce


def scope_path(op_name: str) -> list:
    """The program's scopes among the components of `op_name`, outermost
    first; the last component, the primitive, is left out."""
    return [c for c in op_name.split('/')[:-1] if c in SCOPES]


def _idle_by_span(gaps: list, spans: list) -> collections.Counter:
    """Seconds of each gap under the innermost span open over them."""
    out = collections.Counter()
    for lo, hi in gaps:
        inside = [e for e in spans if e.end > lo and e.start < hi]
        cuts = sorted({lo, hi} | {min(max(t, lo), hi) for e in inside
                                  for t in (e.start, e.end)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            open_ = [e for e in inside if e.start <= mid <= e.end]
            name = (min(open_, key=lambda e: e.end - e.start).name
                    if open_ else NO_SPAN)
            out[name] += b - a
    return out


def reduce(sample: Sample) -> dict:
    """Seconds of the sample, each averaged over the devices:

      seconds      the SAMPLE_SPAN span
      devices      how many devices the trace holds
      busy_s       the union of device operation intervals inside it
      scope_s      {scope: device self seconds of the operations whose
                   innermost scope it is}, UNSCOPED for the rest
      inclusive_s  {scope: device self seconds of the operations inside
                   it at any depth}
      idle_s       {span: idle seconds under it as the innermost open
                   program span}, NO_SPAN where none was open
      span_s       {span: host seconds of the program span, summed}
    """
    spans = [e for e in sample.host if e.name == SAMPLE_SPAN]
    if not spans:
        raise ValueError(f'the trace holds no {SAMPLE_SPAN!r} span')
    lo, hi = spans[0].start, spans[0].end
    program = [e for e in sample.host if e.start < hi and e.end > lo
               and e.name.startswith(SPAN_PREFIXES)]
    span_s = collections.Counter()
    for e in program:
        span_s[e.name] += min(e.end, hi) - max(e.start, lo)
    busy, scope_s, inclusive_s, idle_s = (0.0, collections.Counter(),
                                          collections.Counter(),
                                          collections.Counter())
    for ops in sample.devices.values():
        clipped = [devtrace.Event(max(o.start, lo), min(o.end, hi), i)
                   for i, o in enumerate(ops) if o.end > lo and o.start < hi]
        merged = devtrace.merge(clipped, lo, hi)
        busy += sum(e - s for s, e in merged)
        for i, sec in devtrace.self_times(clipped).items():
            op = ops[i]
            path = scope_path(sample.op_names.get(op.module, {})
                              .get(op.name, ''))
            scope_s[path[-1] if path else UNSCOPED] += sec
            for name in set(path):
                inclusive_s[name] += sec
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        idle_s.update(_idle_by_span(
            [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]], program))
    n = max(1, len(sample.devices))
    return {'seconds': hi - lo, 'devices': len(sample.devices),
            'busy_s': busy / n,
            'scope_s': {k: v / n for k, v in scope_s.items()},
            'inclusive_s': {k: v / n for k, v in inclusive_s.items()},
            'idle_s': {k: v / n for k, v in idle_s.items()},
            'span_s': dict(span_s)}


# ---------------------------------------------------------------- sample


def _log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _twin(job):
    """A shallow copy of `job` whose window leaves `job` as it was. A job
    keeps the records of its window in private list attributes (`_done`,
    `_finished`): the copy starts those empty, so its counts are its own;
    public lists (the data sets, their order) are copied."""
    twin = copy.copy(job)
    for k, v in vars(twin).items():
        if isinstance(v, list):
            setattr(twin, k, [] if k.startswith('_') else list(v))
    return twin


def take(job, seconds: float, trace_dir: str) -> tuple:
    """Run `seconds` of the window on a copy of `job` under the profiler;
    returns (the copy's Window, reduce() of its trace).

    An executable loaded from JAX's persistent cache keeps the metadata of
    whichever program was compiled under its key first, and the key leaves
    metadata out: a program that differs from a cached one only in its
    scopes would run with the cached one's op names. So the sample drops
    the in-memory executables and puts metadata into the key, and one
    untraced window on another copy compiles (or loads) the programs as
    they are named now, before the traced one."""
    import jax
    key_flag = 'jax_compilation_cache_include_metadata_in_key'
    was = getattr(jax.config, key_flag)
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        jax.config.update(key_flag, True)
        jax.clear_caches()
        _twin(job).window(0.0)
        twin = _twin(job)
        # Collect what the warm-up and the earlier readers left now, so
        # that a collection does not land inside the traced sample.
        gc.collect()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(SAMPLE_SPAN):
                win = twin.window(seconds)
        finally:
            jax.profiler.stop_trace()
        return win, reduce(load(devtrace.find_xplane(trace_dir)))
    finally:
        jax.config.update(key_flag, was)
        shutil.rmtree(trace_dir, ignore_errors=True)


def per_unit(win, reduced: dict, unit: str) -> dict:
    """The reduction in ms per unit of the sample's work."""
    k = 1e3 / max(1, int(win.counts[unit]))
    return {'units': int(win.counts[unit]), 'unit': unit,
            'devices': reduced['devices'],
            'ms': reduced['seconds'] * k,
            'busy_ms': reduced['busy_s'] * k,
            **{key: {n: s * k for n, s in reduced[key].items()}
               for key in ('scope_s', 'inclusive_s', 'idle_s', 'span_s')}}


def log_sample(s: dict, window_ms: float | None) -> None:
    u = s['unit']
    _log(f"[scopes] sample {s['units']} {u}: {s['ms']!r} ms per unit "
         f"traced (window {window_ms!r}), device busy {s['busy_ms']!r}")
    busy = s['busy_ms'] or float('nan')
    for name in SCOPES + (UNSCOPED,):
        if name in s['scope_s'] or name in s['inclusive_s']:
            own = s['scope_s'].get(name, 0.0)
            _log(f"[scopes] scope {name} {own!r} ms/{u} own "
                 f"({100 * own / busy!r}% of busy), "
                 f"{s['inclusive_s'].get(name, own)!r} with inner scopes")
    for name in sorted(set(s['idle_s']) | set(s['span_s'])):
        _log(f"[scopes] span {name} idle {s['idle_s'].get(name, 0.0)!r} "
             f"ms/{u}, host {s['span_s'].get(name, 0.0)!r} ms/{u}")


def sample(ctx) -> dict | None:
    """The cell's scope sample in ms per unit, taken on the first call of
    a traced run and kept on `ctx`; None in an untraced run."""
    if ctx.trace is None:
        return None
    got = getattr(ctx, 'scope_sample', None)
    if got is None:
        unit = ctx.job.traffic['attempted']
        win, reduced = take(ctx.job, SAMPLE_SECONDS, os.path.join(
            spec.repo_root(), '.bench_trace', 'sample'))
        got = per_unit(win, reduced, unit)
        n = int(ctx.window.counts[unit])
        log_sample(got, 1e3 * ctx.window.seconds / n if n else None)
        ctx.scope_sample = got
    return got


def device_ms(ctx, scope: str) -> float | None:
    """Device ms per unit of `scope` and the scopes inside it; None where
    no operation carries it (a program without the scope)."""
    s = sample(ctx)
    return None if s is None else s['inclusive_s'].get(scope)


def idle_ms(ctx, span: str) -> float | None:
    """Idle ms per unit under `span`; None where the span never ran or
    the trace holds no device."""
    s = sample(ctx)
    if s is None or not s['devices'] or span not in s['span_s']:
        return None
    return s['idle_s'].get(span, 0.0)


def span_ms(ctx, span: str) -> float | None:
    """Host ms per unit of `span`; None where it never ran."""
    s = sample(ctx)
    return None if s is None else s['span_s'].get(span)
