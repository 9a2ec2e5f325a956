"""Device time by the program's own layer names.

The served model runs each layer under a ``jax.named_scope``; the
configuration file lists the names (``scopes``; for DLRM ``sparse`` (G_s)
with ``gather`` and ``pool`` below it, ``dense`` (G_d) with ``mlp`` and
``interaction`` below it).  The compiler keeps the scopes in every
instruction's ``op_name`` metadata (``jit(serve_step)/sparse/gather/...``),
so a trace can be read by those names rather than by source file
(``tracing.op_layers``).  Like ``tracing``, these are pure functions of
the HLO text and of plain ``(name, start_s, end_s)`` tuples.
"""
from __future__ import annotations

import collections
import re

from chipbench import tracing

_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"%?([\w.\-]+)")


def scope_path(op_name: str, names) -> list[str] | None:
    """The scopes of ``names`` in an ``op_name``, outermost first, with its
    last component (the primitive) dropped; with ``names`` None, every
    component but a transformation's (``jit(...)``, ``vmap(...)``).  None
    for a name that was not traced from a jitted function (a parameter's,
    or one the compiler made up): it says nothing about the layer."""
    parts = op_name.split("/")
    if not parts[0].startswith("jit("):
        return None
    if names is None:
        return [p for p in parts[1:-1] if "(" not in p]
    return [p for p in parts[:-1] if p in names]


def _shared(paths: list[list[str]]) -> list[str]:
    out = paths[0]
    for p in paths[1:]:
        n = 0
        while n < min(len(out), len(p)) and out[n] == p[n]:
            n += 1
        out = out[:n]
    return out


def op_scopes(hlo_text: str, names=None) -> dict[str, str]:
    """Entry-computation instruction name -> scope path (``sparse/gather``;
    ``""`` for none).

    An instruction that calls computations (a fusion) takes the deepest
    path shared by the traced instructions inside them, so gather and pool
    fused into one op count as ``sparse``; the compiler labels a fusion
    after its root alone, which can lie in another layer than the rest."""
    comps: dict[str, list[tuple[str, str | None, list[str]]]] = {}
    entry, body = None, None
    for line in hlo_text.splitlines():
        if body is None:
            if line.rstrip().endswith("{") and not line.startswith(("HloModule", " ")):
                m = _HEAD.match(line)
                body = comps.setdefault(m[2], [])
                entry = m[2] if m[1] else entry
            continue
        if line.startswith("}"):
            body = None
        elif m := _INSTR.match(line):
            op = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            body.append((m[1], op[1] if op else None,
                         _NAME.findall(calls[1]) if calls else []))

    inner: dict[str, list[str] | None] = {}

    def path_of(op: str | None, calls: list[str]) -> list[str] | None:
        called = [p for c in calls if c in comps and (p := comp_path(c)) is not None]
        if called:
            return _shared(called)
        return scope_path(op, names) if op else None

    def comp_path(comp: str) -> list[str] | None:
        if comp not in inner:
            inner[comp] = None  # a computation does not call itself
            paths = [p for _, op, calls in comps[comp]
                     if (p := path_of(op, calls)) is not None]
            inner[comp] = _shared(paths) if paths else None
        return inner[comp]

    return {name: "/".join(path_of(op, calls) or [])
            for name, op, calls in comps.get(entry, [])}


def _window(spans) -> tuple[float, float]:
    win = [(s, e) for n, s, e in spans if n == tracing.WINDOW_SPAN]
    if not win:
        raise ValueError("trace has no window span")
    return win[0]


def scope_seconds(ops, spans, scopes: dict[str, str]) -> dict[str, float]:
    """Device seconds by scope path inside the window, counted as
    ``tracing.reduce`` counts ``layer_s``: each op's duration clipped to
    the window; an op the map lacks counts as ``""``."""
    lo, hi = _window(spans)
    out: dict[str, float] = collections.defaultdict(float)
    for n, s, e in ops:
        if e > lo and s < hi:
            out[scopes.get(n, "")] += min(e, hi) - max(s, lo)
    return dict(out)


def idle_split(ops, modules, spans, step_module: str) -> dict[str, float]:
    """The window's idle seconds (no op running) split into those inside a
    run of the step program (started, no op running yet or in between) and
    those between runs."""
    lo, hi = _window(spans)
    idle = tracing.gaps(tracing.busy_union([(s, e) for _, s, e in ops], lo, hi), lo, hi)
    runs = tracing.busy_union([(s, e) for n, s, e in modules if step_module in n], lo, hi)
    inside, j = 0.0, 0
    for gs, ge in idle:
        while j < len(runs) and runs[j][1] <= gs:
            j += 1
        k = j
        while k < len(runs) and runs[k][0] < ge:
            inside += max(0.0, min(ge, runs[k][1]) - max(gs, runs[k][0]))
            k += 1
    total = sum(e - s for s, e in idle)
    return {"in_step": inside, "between_steps": total - inside}
