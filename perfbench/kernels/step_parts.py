"""Device time by part of the model, where a trace shows ``fusion.<n>``.

The step programs publish which part of the model (the engine's
``MODEL_PARTS``: ``attn_qkv``, ``ffn``, ``moe_router``, ``mixer_in``, ...)
each instruction of each compiled program belongs to, as info series on
``/metrics``:

    llmd_tpu:program_part_ops{program="jit__unified", part="ffn",
                              stale="0", ops="fusion.187 fusion.188"} 2

This reader joins the harness's last scrape before the capture
(``ctx["after"]["engine"]``) with the reduction of the device trace
(``ctx["trace"]["ops"]``: self seconds by operation) and gives the share of
device busy time that the operations of the asked ``parts`` took:

    {"kind": "kernel_roofline", "kernel": "step_parts", "parts": ["ffn", ...]}

A trace names an operation ``<instruction>_<type>_<dims>_`` (``xplane.
short_name``); the instruction is what stands before the type, so
``fusion.20`` does not take ``fusion.208_bf16_64_128_``. An instruction that
several programs hold (``multiply_add_fusion.2`` of the unified step and of
the fused decode call) is booked whole where they agree on its part; where
they do not, each program's seconds come from that program's own list
(``ctx["trace"]["modules"][program]["ops"]``, its forty longest) and the rest
is ``unscoped``. So is every operation no published program holds (a helper
jitted outside the registry, a name a long program's scrape left out), and
what a module without a map lists is taken off a shared name first. The parts
and ``unscoped`` therefore sum to the seconds of all operations, which is
busy time on one device.

With a map and a trace a share is a number, 0.0 where nothing of the parts
ran; with no series (the parent of the PR that brought them), or a program
whose executable names no part (``stale="1"``: loaded from a compile cache
that held it from before the scopes), it is None: nothing rather than a
wrong share.
"""

from __future__ import annotations

import json

SERIES = "llmd_tpu:program_part_ops"
UNSCOPED = "unscoped"


def instruction(trace_name: str) -> str:
    """``fusion.208_bf16_64_17920_`` -> ``fusion.208``; a tuple's ``..`` and
    a name the reduction kept whole (``%x = ...``) are handled too."""
    if " = " in trace_name:
        return trace_name.split(" = ")[0].lstrip("%")
    tokens = trace_name.removesuffix("..").split("_")
    # from the right: the dimensions (digits, or nothing), then the type
    i = len(tokens) - 1
    while i > 0 and (tokens[i] == "" or tokens[i].isdigit()):
        i -= 1
    return "_".join(tokens[:i]) if i > 0 else trace_name


def program_maps(samples: list):
    """``{program: {instruction: part}}`` of a scrape; None where it holds
    no map or a stale one."""
    maps: dict = {}
    for name, labels, _ in samples:
        if name != SERIES:
            continue
        if labels.get("stale") != "0":
            return None
        held = maps.setdefault(labels["program"], {})
        for op in labels.get("ops", "").split():
            held[op] = labels["part"]
    return maps or None


def seconds_by_part(ctx: dict, unscoped: dict | None = None):
    """``{part: self seconds}`` over every operation of the trace
    (``unscoped`` included), or None (module docstring). ``unscoped``, a
    dict, is filled with the unscoped seconds by trace name."""
    tr = ctx.get("trace")
    maps = program_maps((ctx.get("after") or {}).get("engine", []))
    if not tr or "ops" not in tr or not maps:
        return None
    modules = tr.get("modules", {})
    by: dict = {}

    def book(part, secs, name=None):
        if secs > 0:
            by[part] = by.get(part, 0.0) + secs
            if part == UNSCOPED and unscoped is not None:
                unscoped[name] = unscoped.get(name, 0.0) + secs

    for name, op in tr["ops"].items():
        secs, ins = op["seconds"], instruction(name)
        holders = {p: m[ins] for p, m in maps.items() if ins in m}
        # what programs without a map ran under this name is not theirs
        other = min(secs, sum(
            md.get("ops", {}).get(name, {}).get("seconds", 0.0)
            for mod, md in modules.items() if mod not in maps))
        book(UNSCOPED, other, name)
        secs -= other
        if not holders or set(holders.values()) == {UNSCOPED}:
            book(UNSCOPED, secs, name)
        elif len(set(holders.values())) == 1:
            book(next(iter(holders.values())), secs)
        else:
            for prog, part in holders.items():
                mine = min(secs, modules.get(prog, {}).get("ops", {}).get(
                    name, {}).get("seconds", 0.0))
                book(part, mine)
                secs -= mine
            book(UNSCOPED, secs, name)
    return by


def roofline(src: dict, ctx: dict):
    """Share of device busy time, in [0, 1], of the operations of
    ``src["parts"]``; None where there is nothing to read. The join is made
    once a run (kept in ``ctx``), and told once: a ``device_time_by_part``
    line with every part's seconds and the longest unscoped operations, for
    ``PERF.md``'s tables."""
    if "step_parts" not in ctx:
        unscoped: dict = {}
        by = ctx["step_parts"] = seconds_by_part(ctx, unscoped)
        if by is not None:
            top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:12]
            print(json.dumps({
                "note": "device_time_by_part",
                "busy_s": ctx["trace"].get("busy_s"),
                "seconds": {p: round(s, 6) for p, s in sorted(by.items())},
                "unscoped_top": [[n, round(s, 6)] for n, s in top]}),
                flush=True)
    by = ctx["step_parts"]
    busy = (ctx.get("trace") or {}).get("busy_s")
    if by is None or not busy:
        return None
    return sum(by.get(p, 0.0) for p in src["parts"]) / busy
