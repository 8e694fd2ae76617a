"""From a profiler trace (``.xplane.pb``) to numbers: device busy time, the
device operations and programs that took it, and the idle gaps named by what
the host was doing in them.

    python perfbench/xplane.py <file.xplane.pb> [--start-ns A --end-ns B]

prints one JSON object. Run as a process of its own with JAX held to the CPU:
reading a trace needs ``jax.profiler.ProfileData`` and no device, and the
benchmark's parent must stay off JAX.

What a trace of this program on a TPU holds (looked at by hand, PR 23): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per execution of a jitted program, named ``jit_<function>(<fingerprint>)``),
``XLA Ops`` (one event per HLO operation on the device, nested: a ``while``
or a fusion encloses the operations it runs) and ``Steps``; and one plane
``/host:CPU`` with a line per host thread, where the program's
``jax.profiler.TraceAnnotation`` spans (``llmd.unified``,
``llmd.decode_dispatch``, ``llmd.decode_process``, ``llmd.pack_overlap``)
appear by name.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


_HLO = re.compile(r"^%?([\w.\-]+) = (\(?)(\w+)\[([\d,]*)\]")


def short_name(name: str) -> str:
    """A device operation's event carries its whole HLO line. Keep the
    operation's name and its (first) result's type and shape:
    ``%fusion.208 = bf16[64,17920]{...} fusion(...)`` becomes
    ``fusion.208_bf16_64_17920_``, a tuple result ends in ``..``. The text
    after that names the operands, and a pattern meant for a kernel would
    match every fusion that reads the kernel's result."""
    m = _HLO.match(name)
    if not m:
        return name
    dims = m.group(4).replace(",", "_")
    return f"{m.group(1)}_{m.group(3)}_{dims}_" + (".." if m.group(2) else "")


def read(path: str) -> list:
    """[(plane name, [(line name, [(name, start_ns, duration_ns)])])]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (short_name(ev.name) if dev else ev.name,
                 int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events]))
        out.append((plane.name, lines))
    return out


def union_ns(spans: list) -> int:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(spans):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(spans: list, start: int, end: int) -> list:
    """The idle intervals of [start, end) left by the union of ``spans``."""
    out, at = [], start
    for s, e in sorted(spans):
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
        if at >= end:
            break
    if at < end:
        out.append((at, end))
    return [(a, b) for a, b in out if b > a]


def self_times(events: list) -> list:
    """(name, self nanoseconds) of nested device operations: an event's own
    time is its duration less that of the events directly inside it. A
    ``while`` that runs k decode steps encloses every operation of those
    steps; counting both whole would count the time twice."""
    out, stack = [], []  # stack of [name, end, duration, children]

    def close():
        name, _, dur, kids = stack.pop()
        out.append((name, max(0, dur - kids)))

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            close()
        if stack:
            stack[-1][3] += d
        stack.append([name, s + d, d, 0])
    while stack:
        close()
    return out


def reduce(planes: list, start_ns: int | None = None,
           end_ns: int | None = None) -> dict:
    dev = [(n, ls) for n, ls in planes if DEVICE_PLANE.match(n)]
    host = [(n, ls) for n, ls in planes if n.startswith("/host:")]
    res: dict = {"planes": [n for n, _ in planes], "devices": len(dev)}
    if not dev:
        return res
    per_dev = []
    for name, lines in dev:
        ops = [e for ln, evs in lines if ln == "XLA Ops" for e in evs]
        mods = [e for ln, evs in lines if ln == "XLA Modules" for e in evs]
        per_dev.append((name, ops, mods))
    all_ops = [e for _, ops, _ in per_dev for e in ops]
    if not all_ops:
        return res
    lo = start_ns if start_ns is not None else min(s for _, s, _ in all_ops)
    hi = end_ns if end_ns is not None else max(s + d for _, s, d in all_ops)
    res["window_s"] = (hi - lo) / 1e9
    busy, op_tot, mod_tot, mod_ops = [], {}, {}, {}
    for _, ops, mods in per_dev:
        ops = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
               for n, s, d in ops if s + d > lo and s < hi]
        busy.append(union_ns([(s, s + d) for _, s, d in ops]) / 1e9)
        for n, d in self_times(ops):
            t = op_tot.setdefault(n, [0, 0])
            t[0] += 1
            t[1] += d
        # the operations of each program: those that start inside one of
        # its executions (programs do not overlap on one device)
        runs = sorted((s, s + d, re.sub(r"\(.*\)$", "", n))
                      for n, s, d in mods)
        starts = [r[0] for r in runs]
        inside: dict = {}
        for n, s, d in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                inside.setdefault(runs[i][2], []).append((n, s, d))
        for mod, evs in inside.items():
            tot = mod_ops.setdefault(mod, {})
            for n, d in self_times(evs):
                t = tot.setdefault(n, [0, 0])
                t[0] += 1
                t[1] += d
        for n, s, d in mods:
            if s + d <= lo or s >= hi:
                continue
            short = re.sub(r"\(.*\)$", "", n)
            t = mod_tot.setdefault(short, [0, 0, 0, 0])
            t[0] += 1
            t[1] += min(s + d, hi) - max(s, lo)
            # executions wholly inside the window, for a time per execution
            if s >= lo and s + d <= hi:
                t[2] += 1
                t[3] += d
    res["busy_s"] = sum(busy) / len(busy)
    res["idle_share"] = 1.0 - res["busy_s"] / res["window_s"]
    res["ops"] = {n: {"count": c, "seconds": ns / 1e9}
                  for n, (c, ns) in sorted(op_tot.items(),
                                           key=lambda kv: -kv[1][1])}
    res["modules"] = {n: {"count": c, "seconds": ns / 1e9, "whole": w,
                          "whole_seconds": wns / 1e9,
                          "ops": {o: {"count": oc, "seconds": ons / 1e9}
                                  for o, (oc, ons) in sorted(
                                      mod_ops.get(n, {}).items(),
                                      key=lambda kv: -kv[1][1])[:40]}}
                      for n, (c, ns, w, wns) in mod_tot.items()}
    # idle gaps of the first device, named by what the host was doing at
    # the gap's middle: the innermost llmd.* span that covers it; failing
    # that, on the thread that carries those spans (the engine's step loop),
    # the function that ``step`` (or, between steps, the loop itself) had
    # called, from the profiler's Python frames
    _, ops0, _ = per_dev[0]
    spans = [(s, s + d) for _, s, d in ops0 if s + d > lo and s < hi]
    host_lines = [evs for _, lines in host for _, evs in lines]
    notes = [(n, s, s + d) for evs in host_lines
             for n, s, d in evs if n.startswith("llmd.")]
    loop = max(host_lines, default=[], key=lambda evs: sum(
        1 for n, _, _ in evs if n.startswith("llmd.")))
    frames = sorted((s, s + d, n) for n, s, d in loop if n.startswith("$"))
    starts = [f[0] for f in frames]

    def frame_name(mid: int) -> str:
        # frames that cover mid, outermost first (a frame covers its callees)
        # (a frame that covers mid started before it, and few thousand
        # frames back at most: the step loop's calls are short)
        at = bisect.bisect_right(starts, mid)
        cover = [f for f in frames[max(0, at - 4000):at] if f[1] > mid]
        cover.sort(key=lambda f: f[0] - f[1])
        names = [re.sub(r"^\$\S*:\d+ ", "", n) for _, _, n in cover]
        files = [n for _, _, n in cover]
        for i, full in enumerate(files):
            if re.search(r"engine\.py:\d+ step$", full):
                return "step>" + (names[i + 1] if i + 1 < len(names) else "")
        # between steps (the loop's own frame was entered before the trace
        # began, so it is not in it): the loop's outermost call, if any
        return "loop>" + names[0] if names else "no_annotation"

    by = {}
    for a, b in gaps_ns(spans, lo, hi):
        mid = (a + b) // 2
        cover = [(e - s, n) for n, s, e in notes if s <= mid < e]
        name = min(cover)[1] if cover else frame_name(mid)
        by[name] = by.get(name, 0) + (b - a)
    res["idle_gaps"] = {n: ns / 1e9 for n, ns in
                        sorted(by.items(), key=lambda kv: -kv[1])}
    return res


def structure(planes: list) -> dict:
    """What a trace holds, for looking at one by hand."""
    out = {}
    for pname, lines in planes:
        out[pname] = {}
        for lname, evs in lines:
            names = {}
            for n, _, d in evs:
                t = names.setdefault(n, [0, 0])
                t[0] += 1
                t[1] += d
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            out[pname][lname] = {"events": len(evs),
                                 "top": [[n, c, ns / 1e9] for n, (c, ns) in top]}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--start-ns", type=int, default=None)
    ap.add_argument("--end-ns", type=int, default=None)
    ap.add_argument("--structure", action="store_true")
    args = ap.parse_args()
    planes = read(args.path)
    out = structure(planes) if args.structure else reduce(
        planes, args.start_ns, args.end_ns)
    json.dump(out, sys.stdout)
    print()


if __name__ == "__main__":
    main()
