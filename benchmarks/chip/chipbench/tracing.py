"""Reduction of a profiler trace to device busy time, idle gaps and layers.

A trace is reduced in three steps, each a pure function of plain tuples so
that a small synthetic trace can check it:

1. ``op_layers``: the compiled step's HLO text names the source file of
   every instruction (its ``stack_frame_id`` points into the module's
   ``FileNames`` / ``FileLocations`` / ``StackFrames`` tables).  An op is
   given the layer of the innermost frame whose file ends with one of the
   layer's paths; an op with no such frame is ``other``.
2. ``busy_union``: the union of the device-op intervals inside the traced
   window; busy seconds are its length, and the idle share is one minus
   busy over the window.
3. ``idle_by_span``: every gap between busy intervals is split over the
   benchmark's host spans that overlap it (``assemble``, ``device_put``,
   ``dispatch``, ``wait``, ``readback``, ``until_due``); time in none is
   ``host_other``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

HOST_SPANS = ("assemble", "device_put", "dispatch", "wait", "readback",
              "until_due")
WINDOW_SPAN = "window"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?stack_frame_id=(\d+)")
_FILE = re.compile(r'^(\d+)\s+"(.*)"\s*$')
_LOC = re.compile(r"^(\d+)\s+\{file_name_id=(\d+)")
_FRAME = re.compile(r"^(\d+)\s+\{file_location_id=(\d+)\s+parent_frame_id=(\d+)")


def op_layers(hlo_text: str, layer_files: dict[str, list[str]]) -> dict[str, str]:
    """HLO instruction name -> layer, for instructions that name a frame."""
    files, locs, frames = {}, {}, {}
    table = None
    for line in hlo_text.splitlines():
        head = line.strip()
        if head in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            table = head
            continue
        if not head:
            table = None
            continue
        if table == "FileNames" and (m := _FILE.match(head)):
            files[int(m[1])] = m[2]
        elif table == "FileLocations" and (m := _LOC.match(head)):
            locs[int(m[1])] = int(m[2])
        elif table == "StackFrames" and (m := _FRAME.match(head)):
            frames[int(m[1])] = (int(m[2]), int(m[3]))

    def layer_of_file(path: str) -> str | None:
        for layer, suffixes in layer_files.items():
            if any(path.endswith(s) for s in suffixes):
                return layer
        return None

    def layer_of_frame(fid: int) -> str:
        seen = set()
        while fid in frames and fid not in seen:
            seen.add(fid)
            loc, parent = frames[fid]
            layer = layer_of_file(files.get(locs.get(loc, -1), ""))
            if layer:
                return layer
            fid = parent
        return "other"

    out = {}
    for line in hlo_text.splitlines():
        if m := _INSTR.match(line):
            out[m[1]] = layer_of_frame(int(m[2]))
    return out


def busy_union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged (start, end) intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_by_span(idle, spans) -> dict[str, float]:
    """Seconds of the idle gaps covered by each host span.  The spans come
    from one thread and do not overlap; idle time in none is ``host_other``."""
    spans = sorted(spans, key=lambda x: x[1])
    out: dict[str, float] = collections.defaultdict(float)
    j = 0
    for gs, ge in idle:
        while j < len(spans) and spans[j][2] <= gs:
            j += 1
        covered, k = 0.0, j
        while k < len(spans) and spans[k][1] < ge:
            name, s, e = spans[k]
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                out[name] += overlap
                covered += overlap
            k += 1
        out["host_other"] += max(0.0, ge - gs - covered)
    return {k: v for k, v in out.items() if v > 0}


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    launches: int                     # executions of the step in the window
    layer_s: dict[str, float]         # device seconds by layer
    top_ops: list[tuple[str, float]]  # (layer:op, seconds), longest first
    idle_by_span: dict[str, float]    # idle seconds by host span
    # device seconds by scope path (``scopes.scope_seconds``)
    scope_s: dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(ops, modules, spans, layers: dict[str, str], step_module: str) -> Reduced:
    """``ops``: (name, start_s, end_s) device ops; ``modules``: (name,
    start_s, end_s) device program runs; ``spans``: (name, start_s, end_s)
    host spans, one of them ``window``.  Times share one clock."""
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("trace has no window span")
    lo, hi = win[0]
    ops = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
    busy = busy_union([(s, e) for _, s, e in ops], lo, hi)
    busy_s = sum(e - s for s, e in busy)
    per_op: dict[str, float] = collections.defaultdict(float)
    layer_s: dict[str, float] = collections.defaultdict(float)
    for n, s, e in ops:
        dur = min(e, hi) - max(s, lo)
        layer = layers.get(n, "other")
        per_op[f"{layer}:{n}"] += dur
        layer_s[layer] += dur
    launches = sum(1 for n, s, e in modules
                   if s >= lo and s < hi and step_module in n)
    host = [(n, s, e) for n, s, e in spans if n in HOST_SPANS]
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(window_s=hi - lo, busy_s=busy_s, launches=launches,
                   layer_s=dict(layer_s), top_ops=top,
                   idle_by_span=idle_by_span(gaps(busy, lo, hi), host))


_OP_NAME = re.compile(r"^%?([\w.\-]+)")


def events(data, device_plane: str = "/device:TPU:0"):
    """(ops, modules, spans) of a ``jax.profiler.ProfileData``, in seconds
    on the trace's one clock: the device's ``XLA Ops`` by HLO instruction
    name, its ``XLA Modules`` (one per program run) and the host's
    benchmark spans."""
    ops, modules, spans = [], [], []
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in data.planes:
        if plane.name == device_plane:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        ops.append((_OP_NAME.match(e.name)[1], s, s + e.duration_ns * 1e-9))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        modules.append((e.name, s, s + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9))
    return ops, modules, spans


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, found {files}")
    return files[0]
