"""Readings from the marks the program itself leaves in a `--trace 1`
run's profile: its host spans (`telemetry.annotate`, on the profiler's
clock) and the scope names in its compiled programs
(`jax.named_scope`, a Pallas kernel's `name`). The traced run's
`.xplane.pb` lies in `harness.TRACE_DIR` when the per-layer readers run
(each traced run replaces the last); it is opened once and reduced to a
`Trace`, which every reader in `layer_metrics/` shares.

What a `Trace` holds, all on the profiler's one clock, in nanoseconds:

- `spans`: the scheduler's line of the `/host:CPU` plane, found as the
  line that holds the `serve.iteration` events (a Python thread's line
  is called `python3`, not by the thread's name), each span with its
  name, start, end and stats;
- `executions`: the program executions of the first device plane's
  `XLA Modules` line, `(program, program id, start, end)`; the first
  and the last of the line may be cut by the slice's edges and are left
  out of every per-execution number;
- `ops`: the operations of its `XLA Ops` line with, for each, its self
  time (less what is nested inside it: a `while` holds its body), the
  execution it ran in, and its scope path. The path is the `tf_op` stat
  of the operation's metadata (`jit(_decode_greedy)/decode_layers/
  while/body/closed_call/decode_attention/...`; a fusion carries its
  root's), which `jax.profiler.ProfileData` does not hand out, so
  `op_scopes` reads it from the file's event metadata itself;
- `idle`: the intervals of the slice in which no operation ran.

A new reader is a file `layer_metrics/<metric>.py` of two or three
lines: `from benchmark import span_readings` and a `read(run)` that
calls `span_readings.trace(run)` and one of the functions below
(`execution_ms`, `scope_ms`, `host_gap_ms_per_iter`, `kernel_calls`),
or walks `trace.ops` / `trace.spans` itself: exposed collective time is
the self time of the collective operations less their overlap with the
union of the others. A span or scope the trace does not hold (the
parent commit's program has none) gives `None`, never zero.
"""

import bisect
import functools
import os
import re
import statistics

from . import harness, trace_reduce

DECODE_PROGRAMS = ("jit__decode_greedy", "jit__decode_sampled")
PREFILL_PROGRAMS = ("jit__prefill",)
TRAIN_PROGRAMS = ("jit_step",)
ITERATION = "serve.iteration"
# the program's own spans on the scheduler's line; the Python tracer's
# frames (`$array.py:631 _value`) lie on the same line and are left out
OWN_SPANS = ("serve.", "engine.")
# inside these the host waits for the device: idle there is the
# device's own doing (nothing was queued), not the host's
FETCH_SPANS = ("engine.decode.fetch", "engine.first_token.fetch")
# the scopes inside `decode_layers`; what lies under it and under none
# of them is the scan carrying, slicing and copying the cache
DECODE_INNER = ("attn_qkv", "kv_cache_update", "decode_attention",
                "attn_out", "ffn", "moe_router", "moe_dispatch",
                "moe_experts", "moe_combine")
# every scope the program marks, for the printed shares: an operation
# counts under the innermost of these on its path
SCOPES = DECODE_INNER + (
    "decode_layers", "layers", "attention", "flash_attention", "loss",
    "optimizer_update")


# ---- the file's event metadata: operation -> scope path ----

def _fields(buf):
    """(field number, value) of one protobuf message: a varint's number,
    or the bytes of a length-delimited field."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
        elif kind == 2:
            size = varint()
            yield key >> 3, buf[i:i + size]
            i += size
        else:   # fixed 64 or 32 bits
            size = 8 if kind == 1 else 4
            yield key >> 3, buf[i:i + size]
            i += size


def op_scopes(path):
    """{program id: {operation's short name: scope path}} of the first
    device plane, from the `tf_op` and `program_id` stats of its event
    metadata (XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5; XEventMetadata.name=2, stats=5; XStat.metadata_id=1,
    uint64=3, int64=4, str=5, ref=7). The lines, which are most of the
    file, are skipped whole."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, stat_names, metas = "", {}, []
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 5:
                entry = dict(_fields(dict(_fields(value)).get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(
                    entry.get(2, b"")).decode()
            elif field == 4:
                metas.append(dict(_fields(value)).get(2, b""))
        if trace_reduce.DEVICE_PLANE.match(name):
            planes[name] = (stat_names, metas)
    if not planes:
        return {}
    stat_names, metas = planes[min(planes)]
    out = {}
    for meta in metas:
        op, scope, program = None, None, None
        for field, value in _fields(meta):
            if field == 2:
                op = trace_reduce.short_name(bytes(value).decode())
            elif field == 5:
                stat = dict(_fields(value))
                what = stat_names.get(stat.get(1))
                if what == "tf_op":
                    scope = (bytes(stat[5]).decode() if 5 in stat
                             else stat_names.get(stat.get(7)))
                elif what == "program_id":
                    program = stat.get(3, stat.get(4))
        if op and scope and program is not None:
            out.setdefault(str(program & (2 ** 64 - 1)), {})[op] = scope
    return out


# ---- the trace, reduced ----

def program_of(module_event_name):
    """`jit__decode_greedy(15512529683084638911)` -> (name, id)."""
    name, _, rest = module_event_name.partition("(")
    return name, rest.rstrip(")")


@functools.lru_cache(maxsize=None)
def components(path):
    """The names on a scope path, outermost first:
    `jit(step)/transpose(jvp(layers))/while/body/attention/flash_attention`
    holds `layers`, `attention` and `flash_attention` among them."""
    return tuple(re.findall(r"[\w.\-]+", path or ""))


def under(path, scope):
    return scope in components(path)


class Trace(object):
    """planes: [(plane name, {line: [(name, start_ns, end_ns) or, for a
    host span, (name, start_ns, end_ns, stats)]})], as
    `trace_reduce.planes_of` gives them but with every host thread's
    line under a key of its own; scopes: as op_scopes() gives them."""

    def __init__(self, planes, scopes):
        devices = sorted((n, l) for n, l in planes
                         if trace_reduce.DEVICE_PLANE.match(n))
        lines = devices[0][1] if devices else {}
        self.executions = sorted(
            (program_of(name) + (start, end)
             for name, start, end in lines.get("XLA Modules", [])),
            key=lambda x: x[2])
        ops = sorted((e[:3] for e in lines.get(trace_reduce.OPS_LINE, [])),
                     key=lambda e: (e[1], -e[2]))
        self.idle, self.window = [], None
        if ops:
            union = trace_reduce.merge((s, e) for _, s, e in ops)
            self.idle = [(a[1], b[0]) for a, b in zip(union, union[1:])]
            self.window = (union[0][0], union[-1][1])
        self.ops = self._own_times(ops, scopes)
        # every name on some operation's scope path
        self.marked = {c for path in {o[3] for o in self.ops if o[3]}
                       for c in components(path)}
        self.spans = []
        for name, host in planes:
            if not name.startswith("/host:CPU"):
                continue
            for events in host.values():
                if sum(e[0] == ITERATION for e in events) > sum(
                        s[0] == ITERATION for s in self.spans):
                    self.spans = sorted(
                        ((e[0], e[1], e[2], e[3] if len(e) > 3 else {})
                         for e in events if e[0].startswith(OWN_SPANS)),
                        key=lambda e: (e[1], -e[2]))

    def _own_times(self, ops, scopes):
        """[(short name, own ns, index of its execution or None, scope
        path or None)]: an operation's time less that of the operations
        nested inside it, so that no nanosecond is counted twice."""
        starts = [x[2] for x in self.executions]
        out, stack = [], []   # stack of [index in out, end]
        for name, start, end in ops:
            while stack and stack[-1][1] <= start:
                stack.pop()
            if stack:
                parent = out[stack[-1][0]]
                parent[1] -= min(end, stack[-1][1]) - start
            i = bisect.bisect_right(starts, start) - 1
            inside = i >= 0 and end <= self.executions[i][3]
            short = trace_reduce.short_name(name)
            scope = (scopes.get(self.executions[i][1], {}).get(short)
                     if inside else None)
            stack.append([len(out), end])
            out.append([short, end - start, i if inside else None, scope])
        return [tuple(o) for o in out]

    def whole(self, programs=None):
        """Indices of the executions (of `programs`) that the slice
        holds whole: all but the line's first and last."""
        return [i for i, x in enumerate(self.executions)
                if (programs is None or x[0] in programs)
                and 0 < i < len(self.executions) - 1]


_opened = {}


def trace(run):
    """The Trace of this run's traced slice, opened once; None where
    the run was not traced or the profiler wrote nothing."""
    if not run.get("trace"):
        return None
    try:
        path = trace_reduce.find_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _opened:
        _opened.clear()
        _opened[key] = from_file(path)
        for line in commentary(_opened[key]):
            print("[spans] " + line, flush=True)
    return _opened[key]


def from_file(path):
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in profile.planes:
        host = plane.name.startswith("/host:CPU")
        if not host and not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for n, line in enumerate(plane.lines):
            if not host and line.name not in ("XLA Modules",
                                              trace_reduce.OPS_LINE):
                continue
            events = []
            for e in line.events:
                start = float(e.start_ns)
                event = (e.name, start, start + float(e.duration_ns))
                if host and not e.name.startswith(OWN_SPANS):
                    continue
                if host:
                    event += (dict(e.stats),)
                events.append(event)
            # two threads' lines may share a name: keep them apart
            lines["%s#%d" % (line.name, n) if host else line.name] = events
        planes.append((plane.name, lines))
    return Trace(planes, op_scopes(path))


# ---- what the readers call ----

def execution_ms(t, programs):
    """Median device time of one whole execution of `programs`."""
    whole = t.whole(programs) if t else []
    if not whole:
        return None
    value = statistics.median(
        (t.executions[i][3] - t.executions[i][2]) * 1e-6 for i in whole)
    print("[spans] %s: %d whole executions in the slice, median %.3f ms"
          % ("/".join(programs), len(whole), value), flush=True)
    return value


def scope_ms(t, programs, scopes, rest_of=None, inner=()):
    """Device time per whole execution of `programs` of the operations
    under any of `scopes`, plus, with `rest_of`, of those under
    `rest_of` and under none of `inner`. None where the trace holds no
    operation under the first of `scopes`."""
    if not t or scopes[0] not in t.marked:
        return None
    whole = set(t.whole(programs))
    if not whole:
        return None
    named = rest = 0.0
    for _, own, execution, scope in t.ops:
        if execution not in whole or not scope:
            continue
        if any(under(scope, s) for s in scopes):
            named += own
        elif rest_of and under(scope, rest_of) and not any(
                under(scope, s) for s in inner):
            rest += own
    value = (named + rest) * 1e-6 / len(whole)
    print("[spans] %s under %s: %.3f ms an execution over %d executions%s"
          % ("/".join(programs), "+".join(scopes), value, len(whole),
             (" (%.3f ms of it under %s and no inner scope)"
              % (rest * 1e-6 / len(whole), rest_of)) if rest_of else ""),
          flush=True)
    return value


def kernel_calls(t, programs, kernel):
    """Executions of the kernel named `kernel` (its operations are
    `%<kernel>.<n>`) per whole execution of `programs`."""
    if not t or kernel not in t.marked:
        return None
    whole = set(t.whole(programs))
    if not whole:
        return None
    calls = sum(1 for name, _, execution, _ in t.ops
                if execution in whole and re.fullmatch(
                    r"%%?%s(\.\d+)?" % re.escape(kernel), name))
    print("[spans] kernel %s: %d executions in %d steps"
          % (kernel, calls, len(whole)), flush=True)
    return calls / len(whole)


def idle_by_span(t):
    """{innermost span of the scheduler's line, or "no span": idle ns},
    and the iterations the slice holds whole."""
    if not t or not t.window or not t.spans:
        return {}, []
    lo, hi = t.window
    iterations = [s for s in t.spans
                  if s[0] == ITERATION and s[1] >= lo and s[2] <= hi]
    # the line cut into pieces, each under its innermost span
    pieces, stack, at = [], [], lo   # (start, end, name)

    def close(upto):
        nonlocal at
        while stack and stack[-1][2] <= upto:
            pieces.append((at, stack[-1][2], stack[-1][0]))
            at = max(at, stack.pop()[2])

    for span in t.spans:
        close(span[1])
        pieces.append((at, span[1], stack[-1][0] if stack else "no span"))
        at = max(at, span[1])
        stack.append(span)
    close(float("inf"))
    pieces.append((at, hi, "no span"))
    pieces = [p for p in pieces if p[1] > p[0]]
    out, j = {}, 0
    for start, end in t.idle:
        while j < len(pieces) and pieces[j][1] <= start:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < end:
            cover = min(end, pieces[k][1]) - max(start, pieces[k][0])
            if cover > 0:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + cover
            k += 1
    return out, iterations


def host_gap_ms_per_iter(t):
    """Device-idle time that falls, on the scheduler's line, inside a
    `serve.iteration` and outside the two fetch spans (the host is
    working and the device waits for it), over the iterations the slice
    holds whole."""
    by_span, iterations = idle_by_span(t)
    if not iterations:
        return None
    working = sum(ns for name, ns in by_span.items()
                  if name != "no span" and name not in FETCH_SPANS)
    value = working * 1e-6 / len(iterations)
    print("[spans] idle while the scheduler worked: %.3f ms over %d "
          "iterations" % (working * 1e-6, len(iterations)), flush=True)
    return value


def scope_shares(t):
    """{program: {innermost marked scope or "other": share of the
    program's device time}} over its whole executions."""
    out = {}
    whole = {i: t.executions[i][0] for i in t.whole()}
    for _, own, execution, scope in t.ops:
        if execution not in whole:
            continue
        marked = [c for c in components(scope) if c in SCOPES]
        name = marked[-1] if marked else "other"
        program = out.setdefault(whole[execution], {})
        program[name] = program.get(name, 0.0) + own
    return {p: {k: v / sum(d.values()) for k, v in d.items()}
            for p, d in out.items() if sum(d.values()) > 0}


def commentary(t):
    """What is printed once for a trace: idle time by the scheduler's
    span, and each scope's share of its program's device time."""
    by_span, iterations = idle_by_span(t)
    idle = sum(by_span.values())
    if by_span:
        yield ("idle %.4f s of the slice's %.3f s, %d iterations; by the "
               "scheduler's innermost span: %s" % (
                   idle * 1e-9, (t.window[1] - t.window[0]) * 1e-9,
                   len(iterations), ", ".join(
                       "%s %.4f s (%.1f %%)" % (k, v * 1e-9, 100 * v / idle)
                       for k, v in sorted(by_span.items(),
                                          key=lambda kv: -kv[1]))))
    for program, shares in sorted(scope_shares(t).items()):
        if set(shares) == {"other"}:
            continue
        yield "%s device time by scope: %s" % (program, ", ".join(
            "%s %.1f %%" % (k, 100 * v)
            for k, v in sorted(shares.items(), key=lambda kv: -kv[1])))


# ---- the per-layer metrics: layer_metrics/<name>.py names one of these ----

def decode_device_ms(run):
    """engine.decode_device_ms.*: median device time of one execution of
    the decode program."""
    return execution_ms(trace(run), DECODE_PROGRAMS)


def prefill_chunk_device_ms(run):
    """engine.prefill_chunk_device_ms.*: the same of `jit__prefill`."""
    return execution_ms(trace(run), PREFILL_PROGRAMS)


def decode_attention_ms(run):
    """kernels.decode_attention_ms.*: device time under
    `decode_attention` per execution of the decode program."""
    return scope_ms(trace(run), DECODE_PROGRAMS, ("decode_attention",))


def kv_cache_ms(run):
    """kernels.kv_cache_ms.*: device time under `kv_cache_update`, plus
    what lies under `decode_layers` and under none of its inner scopes
    (the scan carrying, slicing and copying the pool), per execution of
    the decode program."""
    return scope_ms(trace(run), DECODE_PROGRAMS, ("kv_cache_update",),
                    rest_of="decode_layers", inner=DECODE_INNER)


def moe_dispatch_ms(run):
    """kernels.moe_dispatch_ms.batch: device time under `moe_router`,
    `moe_dispatch` and `moe_combine` per execution of the decode
    program."""
    return scope_ms(trace(run), DECODE_PROGRAMS,
                    ("moe_dispatch", "moe_router", "moe_combine"))


def moe_experts_ms(run):
    """kernels.moe_experts_ms.batch: the same under `moe_experts`."""
    return scope_ms(trace(run), DECODE_PROGRAMS, ("moe_experts",))


def host_gap_ms(run):
    """scheduler.host_gap_ms_per_iter.*: host_gap_ms_per_iter above."""
    return host_gap_ms_per_iter(trace(run))


def flash_attention_ms(run):
    """kernels.flash_attention_ms.train: device time under
    `flash_attention` per step."""
    return scope_ms(trace(run), TRAIN_PROGRAMS, ("flash_attention",))


def flash_fwd_calls_per_step(run):
    """kernels.flash_fwd_calls_per_step.train: executions of the kernel
    `flash_fwd` per step (the model's layers, twice where remat runs the
    forward again)."""
    return kernel_calls(trace(run), TRAIN_PROGRAMS, "flash_fwd")


# ---- by hand: what a capture holds, and a cut of it for the tests ----

def record(path, first, last):
    """The planes of `path` cut to the executions first..last of its
    `XLA Modules` line and to the lines the readers use, times from the
    cut's start, operations under their short names: what
    `benchmark/tests/data/*_planes.json` hold (`recorded()` reads them back)."""
    t = from_file(path)
    lo, hi = t.executions[first][2], t.executions[last][3]
    profile_ops = []
    import jax

    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    profile_ops = [
                        (trace_reduce.short_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
            break
    names = sorted({o[0] for o in profile_ops if lo <= o[1] and o[2] <= hi})
    index = {n: i for i, n in enumerate(names)}
    ids = {x[1] for x in t.executions[first:last + 1]}
    return {
        "names": names,
        "ops": [[index[n], round(s - lo), round(e - s)]
                for n, s, e in profile_ops if lo <= s and e <= hi],
        "modules": [["%s(%s)" % (x[0], x[1]), round(x[2] - lo),
                     round(x[3] - lo)] for x in t.executions[first:last + 1]],
        "spans": [[s[0], round(s[1] - lo), round(s[2] - lo), s[3]]
                  for s in t.spans if lo <= s[1] and s[2] <= hi],
        "scopes": {p: {n: s for n, s in d.items() if n in index}
                   for p, d in op_scopes(path).items() if p in ids},
    }


def recorded(cut):
    """A Trace from what record() wrote."""
    ops = [(cut["names"][i], s, s + d) for i, s, d in cut["ops"]]
    planes = [("/device:TPU:0", {
        "XLA Modules": [tuple(m) for m in cut["modules"]],
        trace_reduce.OPS_LINE: ops})]
    if cut["spans"]:
        planes.append(("/host:CPU", {"python3#0": [
            tuple(s) for s in cut["spans"]]}))
    return Trace(planes, cut["scopes"])


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("xplane")
    ap.add_argument("--record", help="write the cut here, as JSON")
    ap.add_argument("--executions", type=int, nargs=2, default=(0, 0),
                    help="first and last execution of the cut")
    args = ap.parse_args()
    whole = from_file(args.xplane)
    for n, x in enumerate(whole.executions):
        print("%4d %-28s %12.3f ms" % (n, x[0], (x[3] - x[2]) * 1e-6))
    for text in commentary(whole):
        print(text)
    if args.record:
        with open(args.record, "w") as f:
            json.dump(record(args.xplane, *args.executions), f,
                      separators=(",", ":"))
