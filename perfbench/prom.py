"""Prometheus text exposition, read from outside the program."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> list:
    """[(name, {label: value}, number)] of every sample line."""
    out = []
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line.strip())
        if not m:
            continue
        try:
            v = float(m.group(3))
        except ValueError:
            continue
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")), v))
    return out


def total(samples: list, name: str, labels: dict | None = None):
    """Sum of the samples of ``name`` whose labels include ``labels``; None
    when there is no such series."""
    vs = [v for n, ls, v in samples if n == name
          and all(ls.get(k) == w for k, w in (labels or {}).items())]
    return sum(vs) if vs else None
