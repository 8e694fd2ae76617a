"""Per-layer metrics: each is a data file ``metrics/<name>.json`` whose
``source`` says where its number comes from, and this module is the small set
of readers those files choose from. A reader that finds nothing to read
returns None, and the harness leaves the metric out of the line.

``ctx`` is what one traced run collected:
  gen      facts from the load generator (a dict of numbers)
  before / after   {"engine": samples, "router": samples} at the window's ends
  polls    {"engine": [samples, ...]} taken through the window
  trace    the reduction of the profiler trace (``xplane.reduce``), or None
  device   the engine child's ``/device`` answer after the window
  config   the configuration file
Source kinds:
  generator {field}
  prom_delta {where, series, labels?}          counter's growth in the window
  prom_hist_mean {where, series, labels?, count_labels?}
        growth of ``<series>_sum`` (over every series that has ``labels``)
        over growth of ``<series>_count`` (with ``count_labels``, default
        ``labels``): summing the phases of a per-phase histogram and dividing
        by one phase's count gives a mean per request
  prom_gauge_mean {where, series}              mean of the polled values
  trace {path: [keys...]}                      a number of the reduction
  trace_ops {pattern, of: "busy"|"seconds"}    operations by name pattern
  trace_module {pattern, per: "execution"|"seconds"|"step", step_op?}
        ``step``: the programs' seconds in the capture over the steps they
        ran there, counted as the executions of ``step_op`` inside them: an
        operation that runs once a step, by name pattern, ``{key}`` filled
        from the configuration (the head's logits, ``f32[rows, vocab_size]``)
  kernel_roofline {kernel, pattern, module}    see ``kernels/``
  device {field}
  scale {of, by} / difference {a, b} / ratio {a, b}   arithmetic on sources
  metric {name}                 what ``metrics/<name>.json`` reads: the same
        quantity under a second name, for cells whose end-to-end metric (a
        per-layer metric's ``moves``) is another one
"""

from __future__ import annotations

import importlib
import json
import os
import re

import prom

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def _delta(ctx, where, series, labels):
    a = prom.total(ctx["before"].get(where, []), series, labels)
    b = prom.total(ctx["after"].get(where, []), series, labels)
    if b is None:
        return None
    return b - (a or 0.0)


def read(src: dict, ctx: dict):
    kind = src["kind"]
    if kind == "generator":
        return ctx["gen"].get(src["field"])
    if kind == "prom_delta":
        return _delta(ctx, src["where"], src["series"], src.get("labels"))
    if kind == "prom_hist_mean":
        s = _delta(ctx, src["where"], src["series"] + "_sum",
                   src.get("labels"))
        c = _delta(ctx, src["where"], src["series"] + "_count",
                   src.get("count_labels", src.get("labels")))
        return None if s is None or not c else s / c
    if kind == "prom_gauge_mean":
        vs = [prom.total(p, src["series"])
              for p in ctx["polls"].get(src["where"], [])]
        vs = [v for v in vs if v is not None]
        return sum(vs) / len(vs) if vs else None
    if kind == "device":
        return (ctx.get("device") or {}).get(src["field"])
    if kind == "metric":
        return read(load(src["name"])["reads"], ctx)
    if kind in ("scale", "difference", "ratio"):
        if kind == "scale":
            v = read(src["of"], ctx)
            return None if v is None else v * src["by"]
        a, b = read(src["a"], ctx), read(src["b"], ctx)
        if a is None or b is None:
            return None
        if kind == "difference":
            return a - b
        return None if not b else a / b
    tr = ctx.get("trace")
    if not tr or "ops" not in tr:
        return None
    if kind == "trace":
        v = tr
        for k in src["path"]:
            if not isinstance(v, dict) or k not in v:
                return None
            v = v[k]
        return v
    if kind == "trace_ops":
        pat = re.compile(src["pattern"])
        secs = sum(o["seconds"] for n, o in tr["ops"].items() if pat.search(n))
        if not secs:
            return None
        return secs / tr["busy_s"] if src["of"] == "busy" else secs
    if kind == "trace_module":
        pat = re.compile(src["pattern"])
        ms = [m for n, m in tr["modules"].items() if pat.search(n)]
        n = sum(m["count"] for m in ms)
        if not n:
            return None
        v = sum(m["seconds"] for m in ms)
        if src["per"] == "step":
            # a fused call runs as many steps as its rows' budgets give it
            # (engine.decode_steps is only the cap), so the steps are counted
            # where they ran; of several operations that match, each runs
            # once a step and the most often seen has them all
            op = re.compile(src["step_op"].format(**ctx["config"]))
            steps = sum(max((o["count"] for n, o in m.get("ops", {}).items()
                             if op.search(n)), default=0) for m in ms)
            return v / steps if steps else None
        if src["per"] == "execution":
            # executions the capture holds whole, where it holds any: one
            # that its edge cut is shorter than it was
            whole = sum(m.get("whole", 0) for m in ms)
            if whole:
                v, n = sum(m["whole_seconds"] for m in ms), whole
            v /= n
        return v
    if kind == "kernel_roofline":
        mod = importlib.import_module("kernels." + src["kernel"])
        return mod.roofline(src, ctx)
    raise ValueError(f"unknown source kind {kind!r}")
