"""From the profiler's xplane file to busy time, per-op time and gaps.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain tuples; ``reduce`` works on those alone, so a recorded trace checks
it. A device is a plane named ``/device:TPU:<n>``; its operations are
the events of its ``XLA Ops`` line, each named by its whole HLO line
(readers match on that text; the breakdown prints ``short_name``). Busy time is the union of those
events inside the window; an op's time is its self time (a ``while``
does not count its body twice). The window is the bench's own
``perfbench.window`` annotation, which the profiler puts on the same
clock as the device. Idle time goes to the ``client.*`` annotation that
covers it — what the client was waiting for — or to ``between-requests``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "perfbench.window"
CLIENT = "client."


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """-> {plane: {line: [(name, start_ns, duration_ns), ...]}}: the
    devices' op lines whole, of the host only the bench's annotations."""
    from jax.profiler import ProfileData

    def keep(name):
        return name == WINDOW or name.startswith(CLIENT)

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events if device or keep(e.name)]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            planes[plane.name] = lines
    return planes


def short_name(text: str) -> str:
    """The trace prints an op as its whole HLO line; keep the op's name,
    its opcode (and fusion kind) and its first result shape."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text[:80]
    op = re.search(r"(?<![\w.%\-])([a-z][a-z0-9\-]*)\(", rest)
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    kind = re.search(r"kind=(\w+)", rest)
    parts = [name.lstrip("%"), op.group(1) if op else "?"]
    if kind:
        parts.append(kind.group(1))
    if shape:
        parts.append(shape.group(0))
    return " ".join(parts)[:80]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """{name: ns}: each event's duration less what its children cover."""
    total = defaultdict(float)
    stack = []  # (end, name)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            total[stack[-1][1]] -= dur
        total[name] += dur
        stack.append((start + dur, name))
    return total


def reduce(planes: dict, requests: int = 1) -> dict:
    host = [e for p, lines in planes.items() if not DEVICE_PLANE.match(p)
            for evs in lines.values() for e in evs]
    windows = [(s, s + d) for n, s, d in host if n == WINDOW]
    devices = {p: lines.get(OPS_LINE, []) for p, lines in planes.items()
               if DEVICE_PLANE.match(p)}
    if not devices or not any(devices.values()):
        raise ValueError("trace: no operation ran on a device")
    if windows:
        w0, w1 = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        flat = [e for evs in devices.values() for e in evs]
        w0 = min(s for _, s, _ in flat)
        w1 = max(s + d for _, s, d in flat)
    notes = [(n, s, s + d) for n, s, d in host if n.startswith(CLIENT)]
    out, gaps = [], defaultdict(float)
    for plane in sorted(devices):
        evs = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
               for n, s, d in devices[plane] if s < w1 and s + d > w0]
        busy = _union((s, s + d) for _, s, d in evs)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                cover = defaultdict(list)
                for n, s, e in notes:
                    if e > a and s < b:
                        cover[n].append((max(s, a), min(e, b)))
                held = {n: sum(y - x for x, y in _union(iv))
                        for n, iv in cover.items()}
                # sessions side by side can cover one gap twice: share it
                scale = min(1.0, (b - a) / sum(held.values())) if held else 1.0
                for n, t in held.items():
                    gaps[n] += t * scale / len(devices)
                rest = (b - a) - sum(held.values()) * scale
                if rest > 0:
                    gaps["between-requests"] += rest / len(devices)
        out.append({
            "plane": plane,
            "busy_s": sum(b - a for a, b in busy) * 1e-9,
            "ops": {n: t * 1e-9 for n, t in _self_times(evs).items() if t > 0},
        })
    mean_ops = defaultdict(float)
    for d in out:
        for n, t in d["ops"].items():
            mean_ops[n] += t / len(out)
    top = sorted(mean_ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in out) / len(out),
        "requests": int(requests),
        "devices": out,
        "breakdown": {
            "device_ops": [[short_name(n), t] for n, t in top],
            "idle_gaps": [[n, t * 1e-9] for n, t in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        },
    }
