"""Reduction of a profiler trace to the intervals the metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Each device is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per operation that ran on the core, and its ``Async XLA
Ops`` line the asynchronous ones (collectives, async copies), each with
its start and duration in nanoseconds on the same clock as the host's
annotations. An op's name is its HLO text. The harness marks its window
and its host phases with ``bench.*`` annotations.

Each device op is put in one class:

* ``kernel``: a custom call whose HLO text holds the configuration's
  ``kernel`` key (``tpu_custom_call``: the engine's kernels are the only
  Mosaic kernels of these programs);
* ``collective``: a transfer between chips (collective-permute, all-
  reduce, all-gather, all-to-all, reduce-scatter, send, recv);
* ``other``: everything else (pads, slices, copies, fusions).

Run ``python bench/trace.py <file.xplane.pb>`` to print what a trace holds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import sys

OPS_LINES = ("XLA Ops", "Async XLA Ops")
DEVICE_PREFIX = "/device:TPU:"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter", "send", "recv")


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """``a`` minus ``b``, both merged."""
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


HLO_OP = re.compile(r"(%\S+) = (.*?)\s([a-z][a-z0-9-]*)\(")


def short_name(name: str) -> str:
    """An op's HLO text cut to its name, result type and opcode."""
    m = HLO_OP.match(name)
    return f"{m[1]} = {m[2]} {m[3]}" if m else name[:120]


def classify(name: str, kernel: str) -> str:
    """``kernel``, ``collective`` or ``other``, by the op's opcode (its
    operands may be named after collectives)."""
    m = HLO_OP.match(name)
    opcode = m[3] if m else name.split()[0] if name else ""
    if opcode == "custom-call" and kernel in name:
        return "kernel"
    if opcode.startswith(COLLECTIVES):
        return "collective"
    return "other"


@dataclasses.dataclass
class Device:
    name: str
    ops: list          # (start_ns, end_ns, op name, class), in the window

    def intervals(self, cls=None):
        return merge((s, e) for s, e, _, c in self.ops
                     if cls is None or c == cls)


@dataclasses.dataclass
class Reduced:
    window: tuple      # (start_ns, end_ns) of the bench.window annotation
    devices: list      # Device per chip that ran an op
    host: list         # (start_ns, end_ns, name) of bench.* phases

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return sum(total(d.intervals()) for d in self.devices) \
            / len(self.devices) * 1e-9

    def op_seconds(self, cls: str) -> float:
        """Time in which an op of one class runs, summed over the chips."""
        return sum(total(d.intervals(cls)) for d in self.devices) * 1e-9

    def idle_gaps(self):
        """(start, end, device name) of each gap with no op running."""
        lo, hi = self.window
        for d in self.devices:
            for s, e in subtract([(lo, hi)], d.intervals()):
                yield s, e, d.name

    def host_activity(self, s, e) -> str:
        """The host phase that overlaps ``[s, e)`` the most."""
        best, name = 0, "host: outside bench phases"
        for hs, he, hn in self.host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, hn
        return name

    def breakdown(self, top: int = 10) -> dict:
        per_op = collections.Counter()
        for d in self.devices:
            for s, e, name, _ in d.ops:
                per_op[short_name(name)] += (e - s) * 1e-9 / len(self.devices)
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, v] for n, v in per_op.most_common(top)],
            "idle_gaps": [[f"{self.host_activity(s, e)} ({dev})",
                           (e - s) * 1e-9] for s, e, dev in gaps],
        }


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def reduce_profile(profile, *, kernel: str) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to the window's intervals."""
    host, window = [], None
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name not in OPS_LINES:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append((s, s + int(ev.duration_ns), ev.name,
                                classify(ev.name, kernel)))
            if ops:
                devices.append(Device(plane.name, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("bench."):
                        continue
                    s = int(ev.start_ns)
                    span = (s, s + int(ev.duration_ns), ev.name)
                    if ev.name == "bench.window":
                        window = span[:2]
                    else:
                        host.append(span)
    if window is None:
        raise ValueError("trace holds no bench.window annotation")
    lo, hi = window
    for d in devices:
        d.ops = [(max(s, lo), min(e, hi), n, c) for s, e, n, c in d.ops
                 if min(e, hi) > max(s, lo)]
    devices = [d for d in devices if d.ops]
    if not devices:
        raise ValueError("trace holds no device op inside the window")
    devices.sort(key=lambda d: d.name)
    return Reduced(window, devices, sorted(host))


def find_xplane(directory) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise ValueError(f"no .xplane.pb under {directory}")
    return found[-1]


def reduce_file(path, *, kernel: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), kernel=kernel)


def reduce_dir(directory, *, kernel: str) -> Reduced:
    return reduce_file(find_xplane(directory), kernel=kernel)


def dump(path, *, events: int = 8, out=sys.stdout):
    """Print each plane, line and a few events with their stats."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(str(path)).planes:
        print(f"PLANE {plane.name}", file=out)
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names: {names.most_common(12)}", file=out)
            for ev in evs[:events]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} {_stats(ev)}", file=out)


if __name__ == "__main__":
    dump(sys.argv[1], events=int(sys.argv[2]) if len(sys.argv) > 2 else 8)
