"""From a profiler trace to the benchmark's device numbers.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
a plain, JSON-safe profile:

* ``ops``: every device operation (``[device, program, op, start_ns,
  dur_ns]``) of the ``XLA Ops`` line of the planes named ``/device:...``,
  with the XLA program it belongs to (``hlo_module``, or else the enclosing
  event of the device's ``XLA Modules`` line, e.g. ``jit_sample_blocks``);
* ``host``: the named host events (``[name, start_ns, dur_ns]``) of the
  thread that ran the window: the program's ``Tracer(jax_annotations=True)``
  spans and the runtime's dispatch events;
* ``window``: ``[start_ns, end_ns]`` of the host annotation that brackets
  the traced window.

Everything after that works on the plain profile, so a small recorded one
is enough to test it:

* ``busy_ns``: the union of the op intervals of each device, clipped to
  the window, averaged over devices;
* ``layer_ns``: op time per layer, by the first pattern of ``layers.json``
  that matches the op's program name;
* ``idle_gaps``: each stretch of the window with no op running, labelled
  by the innermost host span open at its middle;
* ``top_ops``: the device operations that took most time.
"""

from __future__ import annotations

import collections
import fnmatch
import glob
import os

WINDOW_SPAN = "bench_window"
NO_SPAN = "(no host span)"


class MissingLayer(RuntimeError):
    """A layer that a traced run must show had no device events."""


def load_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, host, window = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name.split("(")[0])
                for e in lines.get("XLA Modules", [])
            )
            for e in lines.get("XLA Ops", []):
                module = dict(e.stats).get("hlo_module") or _enclosing(modules, e.start_ns)
                op = e.name.split(" = ")[0]
                ops.append([plane.name, str(module), op, e.start_ns, e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(line.events)
                if not any(e.name == WINDOW_SPAN for e in events):
                    continue  # only the thread that ran the window: its spans say what it did
                for e in events:
                    if e.name == WINDOW_SPAN:
                        window = [e.start_ns, e.start_ns + e.duration_ns]
                    elif not e.name.startswith("$") and e.duration_ns > 0:
                        host.append([e.name, e.start_ns, e.duration_ns])
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return {"window": window, "ops": ops, "host": host}


def _enclosing(modules, t) -> str:
    import bisect

    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return "(no module)"


def _clip(start, dur, window):
    a, b = max(start, window[0]), min(start + dur, window[1])
    return (a, b) if b > a else None


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(profile: dict) -> float:
    """Union of op intervals per device inside the window, mean over devices."""
    per_dev = collections.defaultdict(list)
    for dev, _mod, _op, start, dur in profile["ops"]:
        iv = _clip(start, dur, profile["window"])
        if iv:
            per_dev[dev].append(iv)
    if not per_dev:
        return 0.0
    return sum(sum(b - a for a, b in union(v)) for v in per_dev.values()) / len(per_dev)


def window_ns(profile: dict) -> float:
    return float(profile["window"][1] - profile["window"][0])


def layer_of(module: str, patterns) -> str:
    for pattern, layer in patterns:
        if fnmatch.fnmatchcase(module, pattern):
            return layer
    return "(unmapped)"


def layer_ns(profile: dict, layer_map: dict) -> dict[str, float]:
    """Device op time per layer inside the window (mean over devices).
    Raises :class:`MissingLayer` when an expected layer has no events."""
    devices = {op[0] for op in profile["ops"]} or {None}
    out: dict[str, float] = collections.defaultdict(float)
    for _dev, module, _op, start, dur in profile["ops"]:
        iv = _clip(start, dur, profile["window"])
        if iv:
            out[layer_of(module, layer_map["patterns"])] += (iv[1] - iv[0]) / len(devices)
    missing = [name for name in layer_map.get("expected", []) if out.get(name, 0.0) <= 0.0]
    if missing:
        raise MissingLayer(f"no device events for layer(s) {missing}")
    return dict(out)


def idle_gaps(profile: dict) -> list[tuple[str, float]]:
    """Idle time inside the window by the host span open in each gap,
    ``[(label, ns)]`` largest first.

    A gap's label is the shortest host span that covers its midpoint."""
    w0, w1 = profile["window"]
    busy = union(
        iv for dev, _m, _o, s, d in profile["ops"] if (iv := _clip(s, d, profile["window"]))
    )
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    spans = sorted((s, s + d, name) for name, s, d in profile["host"])
    totals: dict[str, float] = collections.defaultdict(float)
    active: list[tuple[float, float, str]] = []
    i = 0
    for a, b in gaps:  # gaps are in time order: one sweep over the spans
        mid = (a + b) / 2
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > mid]
        best = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        totals[best[2] if best else NO_SPAN] += b - a
    return sorted(totals.items(), key=lambda kv: -kv[1])


def top_ops(profile: dict, n: int = 10) -> list[tuple[str, float]]:
    """``[(program:op, ns)]`` of the ops that took most time in the window."""
    totals: dict[str, float] = collections.defaultdict(float)
    for _dev, module, op, start, dur in profile["ops"]:
        iv = _clip(start, dur, profile["window"])
        if iv:
            totals[f"{module}:{op}"] += iv[1] - iv[0]
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]


def trim(profile: dict, max_ops: int = 400, max_host: int = 400) -> dict:
    """A small copy of a profile (the first ops and host spans of its
    window), for keeping as a test fixture."""
    ops = sorted(profile["ops"], key=lambda o: o[3])[:max_ops]
    host = sorted(profile["host"], key=lambda h: h[1])[:max_host]
    end = max([o[3] + o[4] for o in ops] + [profile["window"][0]])
    return {"window": [profile["window"][0], min(end, profile["window"][1])], "ops": ops,
            "host": [h for h in host if h[1] < end]}
