"""From the profiler's trace to numbers: device busy time, the device
operations that took most time, and the longest idle gaps named by what
the host was doing. Reads the `.xplane.pb` with nothing but JAX.

A device plane is one named `/device:TPU:<n>`. Its busy time
is the union of the intervals of its operations line (`XLA Ops`, the
line that holds one event per executed operation; where a trace has no
such line, the union over all its lines but `Steps`, which only spans
them). Host spans are every event of the `/host:CPU` plane's lines.
"""

import glob
import os
import re

# a chip's own plane; `/device:CUSTOM:...` planes hold no operations
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
              "Framework Ops", "Source code")
OWN_PREFIX = "bench."   # the benchmark's own TraceAnnotation spans
TOP = 10
NAMED_GAPS = 300   # the longest gaps are named one by one, the rest lumped


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("the profiler wrote no .xplane.pb under %s"
                                % trace_dir)
    return paths[-1]


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def planes_of(profile):
    """[(plane name, {line name: [(name, start_ns, end_ns)]})]."""
    out = []
    for plane in profile.planes:
        lines = {}
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        out.append((plane.name, lines))
    return out


def short_name(name):
    """An operation's event carries its whole HLO text; its name is what
    stands before ` = `."""
    return name.split(" = ", 1)[0][:64]


def self_times(events):
    """{name: seconds} with each event's time less that of the events
    nested inside it (a `while` holds its body's operations on the same
    line), so that no second is counted under two names."""
    out, stack = {}, []   # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own * 1e-9

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([short_name(name), end, end - start])
    close(float("inf"))
    return out


def device_events(lines):
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    return [e for name, events in lines.items() if name not in SKIP_LINES
            for e in events]


class HostSpans(object):
    """Host spans as arrays, so that naming a gap is a few vector
    operations whatever the trace holds."""

    def __init__(self, spans):
        import numpy as np

        self.names = [s[0] for s in spans]
        self.start = np.array([s[1] for s in spans], float)
        self.end = np.array([s[2] for s in spans], float)
        self.own = np.array([n.startswith(OWN_PREFIX) for n in self.names],
                            bool)

    def name_gap(self, start, end):
        """What the host was doing in an idle gap: the benchmark's own
        span that covers most of it, else the shortest host span over
        its middle."""
        import numpy as np

        if not self.names:
            return "no host span"
        cover = np.minimum(self.end, end) - np.maximum(self.start, start)
        own = np.where(self.own & (cover > 0), cover, 0.0)
        if own.max() > 0:
            return self.names[int(own.argmax())]
        mid = 0.5 * (start + end)
        over = (self.start <= mid) & (mid <= self.end)
        if not over.any():
            return "no host span"
        length = np.where(over, self.end - self.start, np.inf)
        return self.names[int(length.argmin())]


def reduce(planes, window_s, n_devices=None):
    """planes: as planes_of() gives them. Returns busy_s (mean over the
    device planes), window_s, device_ops and idle_gaps. `window_s` is
    the host's reading of the slice and stands only where the trace
    holds no device operation."""
    devices = sorted(((n, l) for n, l in planes if DEVICE_PLANE.match(n)),
                     key=lambda nl: nl[0])
    if n_devices:
        devices = devices[:n_devices]
    host = HostSpans([e for n, l in planes if n.startswith("/host:CPU")
                      for events in l.values() for e in events
                      if e[2] > e[1]])
    if not devices:
        return {"busy_s": 0.0, "window_s": window_s, "device_ops": [],
                "idle_gaps": [], "n_device_planes": 0}
    busy, ops = [], {}
    gaps = {}
    first, last = [], []
    for i, (_, lines) in enumerate(devices):
        events = device_events(lines)
        if events:
            first.append(min(e[1] for e in events))
            last.append(max(e[2] for e in events))
        union = merge((s, e) for _, s, e in events)
        busy.append(sum(e - s for s, e in union) * 1e-9)
        for name, seconds in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + seconds / len(devices)
        if i == 0:
            between = sorted(((nxt - prev_end, prev_end, nxt)
                              for (_, prev_end), (nxt, _)
                              in zip(union, union[1:])), reverse=True)
            for length, start, end in between[:NAMED_GAPS]:
                name = host.name_gap(start, end)
                gaps[name] = gaps.get(name, 0.0) + length * 1e-9
            rest = sum(g[0] for g in between[NAMED_GAPS:]) * 1e-9
            if rest:
                gaps["gaps beyond the %d longest" % NAMED_GAPS] = rest
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    if first:
        # the traced window, on the trace's own clock: from the first
        # device operation's start to the last one's end
        window_s = (max(last) - min(first)) * 1e-9
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "device_ops": top(ops), "idle_gaps": top(gaps),
            "n_device_planes": len(devices)}


def reduce_file(path, window_s, n_devices=None):
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    return reduce(planes_of(profile), window_s, n_devices)


if __name__ == "__main__":
    # what a trace holds, for a reader who has to look before trusting
    import sys

    import jax

    for plane_name, lines in planes_of(
            jax.profiler.ProfileData.from_file(sys.argv[1])):
        print("plane", plane_name)
        for line_name, events in lines.items():
            print("  line %-40s %7d events, first: %s" % (
                line_name, len(events), [e[0][:60] for e in events[:3]]))
