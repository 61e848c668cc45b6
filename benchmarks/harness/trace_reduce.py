"""From a profiler trace to numbers: device busy and idle, busy time per
step, the operations that took the time, collective time and its exposed
part, and the longest idle gaps by what the host was doing.

Two stages, so that the arithmetic can be checked without a chip:

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler wrote (with nothing
but JAX) into a ``Trace``: per device the intervals in which an operation
ran, under the names XLA gives them, and the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` names that start with ``xfb:``), all on
the trace's one clock.  A ``Trace`` goes to and from plain JSON, which is
how a trace recorded on the chip is kept beside the tests.

``reduce`` takes a ``Trace`` and a window and does interval arithmetic.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "xfb:"
COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)\b"
)
Interval = tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    # device id -> [(op name, start_ns, duration_ns)], any order
    devices: dict[int, list[tuple[str, float, float]]]
    # the benchmark's host spans: [(name without prefix, start_ns, duration_ns)]
    spans: list[tuple[str, float, float]]
    # where the device intervals came from: "device_planes", or
    # "host_threads" on a CPU backend (rehearsal), which has no device plane
    source: str = "device_planes"

    def to_json(self) -> dict:
        out = {"source": self.source, "spans": self.spans, "devices": {}}
        for dev, ops in self.devices.items():
            names = sorted({name for name, _, _ in ops})
            index = {name: i for i, name in enumerate(names)}
            out["devices"][str(dev)] = {
                "names": names,
                "ops": [[index[n], s, d] for n, s, d in ops],
            }
        return out

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        devices = {
            int(dev): [(d["names"][i], s, dur) for i, s, dur in d["ops"]]
            for dev, d in doc["devices"].items()
        }
        spans = [tuple(s) for s in doc["spans"]]
        return cls(devices, spans, doc.get("source", "device_planes"))

    def cut(self, t0: float, t1: float) -> "Trace":
        """The part of the trace that starts inside [t0, t1)."""
        return Trace(
            {
                dev: [op for op in ops if t0 <= op[1] < t1]
                for dev, ops in self.devices.items()
            },
            [s for s in self.spans if s[1] + s[2] > t0 and s[1] < t1],
            self.source,
        )


_HLO_RE = re.compile(r"^%?(?P<op>[^ ]+) = \(?(?P<type>[a-z0-9]+\[[0-9,]*\])?")


def short_name(name: str) -> str:
    """A device operation's name in the trace is its whole HLO line; keep the
    operation's own name, its (first) result type and, of a fusion, its kind:
    ``fusion.10 s32[3670016] kCustom``."""
    m = _HLO_RE.match(name)
    if not m or " = " not in name:
        return name
    kind = re.search(r"kind=(k\w+)", name)
    parts = [m.group("op"), m.group("type"), kind.group(1) if kind else None]
    return " ".join(p for p in parts if p)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict[int, list] = {}
    spans: list = []
    host_ops: list = []
    for plane in data.planes:
        m = re.match(r"^/device:[A-Za-z]+:(\d+)$", plane.name)
        if m:
            # one line of the plane holds the operations; the others
            # (modules, steps, trace-me) repeat the same time at other grains
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.setdefault(int(m.group(1)), []).extend(
                        (short_name(ev.name), float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((
                            ev.name[len(SPAN_PREFIX):],
                            float(ev.start_ns), float(ev.duration_ns),
                        ))
                    elif any(k == "hlo_op" for k, _ in ev.stats):
                        host_ops.append(
                            (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        )
    if devices:
        return Trace(devices, sorted(spans, key=lambda s: s[1]))
    # a CPU backend runs its operations on host threads and tags them
    return Trace({0: host_ops}, sorted(spans, key=lambda s: s[1]), "host_threads")


# -- interval arithmetic --------------------------------------------------------


def union(intervals: list[Interval]) -> list[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: list[Interval], t0: float, t1: float) -> list[Interval]:
    return [
        (max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1
    ]


def length(disjoint: list[Interval]) -> float:
    return sum(e - s for s, e in disjoint)


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The points of disjoint sorted ``a`` that are not in disjoint sorted
    ``b``."""
    out = []
    j = 0
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


def span_window(trace: Trace, name: str) -> Interval:
    """The interval of the one host span called ``name``."""
    found = [(s, s + d) for n, s, d in trace.spans if n == name]
    if len(found) != 1:
        raise ValueError(f"{len(found)} spans named {name!r} in the trace")
    return found[0]


def label_of(trace: Trace, t: float, labels: dict[str, str], default: str) -> str:
    """What the host was doing at ``t``: the label of the innermost (latest
    started) labelled span that covers it."""
    best = None
    for name, s, d in trace.spans:
        if name in labels and s <= t < s + d and (best is None or s >= best[0]):
            best = (s, labels[name])
    return best[1] if best else default


def reduce(
    trace: Trace,
    window: Interval,
    steps: int | None = None,
    labels: dict[str, str] | None = None,
    default_label: str = "unlabelled",
    top: int = 10,
    gaps: int = 5,
) -> dict:
    """The numbers of one traced window.  Times in seconds.

    ``device_idle_frac`` is the worst device's; ``busy_s`` is the mean over
    devices (what the result line's ``device.busy_s`` asks for).  Collective
    numbers are the first device's.  ``labels`` maps span names to the words
    idle gaps are reported under.
    """
    t0, t1 = window
    if t1 <= t0 or not trace.devices:
        raise ValueError("empty window or no device in the trace")
    per_device = {}
    for dev, ops in sorted(trace.devices.items()):
        busy = union(clip([(s, s + d) for _, s, d in ops], t0, t1))
        per_device[dev] = busy
    busy_s = {dev: length(b) / 1e9 for dev, b in per_device.items()}
    window_s = (t1 - t0) / 1e9
    worst = min(busy_s, key=busy_s.get)

    totals: dict[str, float] = {}
    for ops in trace.devices.values():
        for name, s, d in ops:
            inside = min(s + d, t1) - max(s, t0)
            if inside > 0:
                totals[name] = totals.get(name, 0.0) + inside
    ndev = len(trace.devices)
    device_ops = [
        [name, ns / 1e9 / ndev]
        for name, ns in sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    ]

    first = min(trace.devices)
    coll = [
        (s, s + d) for n, s, d in trace.devices[first] if COLLECTIVE_RE.search(n)
    ]
    rest = [
        (s, s + d) for n, s, d in trace.devices[first]
        if not COLLECTIVE_RE.search(n)
    ]
    coll_u = union(clip(coll, t0, t1))
    collective_s = length(coll_u) / 1e9
    exposed_s = length(subtract(coll_u, union(clip(rest, t0, t1)))) / 1e9

    idle = subtract([(t0, t1)], per_device[worst])
    by_label: dict[str, float] = {}
    labelled = []
    for s, e in idle:
        lab = label_of(trace, (s + e) / 2, labels or {}, default_label)
        by_label[lab] = by_label.get(lab, 0.0) + (e - s) / 1e9
        labelled.append((e - s, lab))
    idle_gaps = [
        [f"total:{lab}", sec]
        for lab, sec in sorted(by_label.items(), key=lambda kv: -kv[1])
    ] + [
        [f"gap:{lab}", ns / 1e9]
        for ns, lab in sorted(labelled, reverse=True)[:gaps]
    ]

    out = {
        "window_s": window_s,
        "busy_s": sum(busy_s.values()) / ndev,
        "busy_s_by_device": busy_s,
        "device_idle_frac": 1.0 - busy_s[worst] / window_s,
        "collective_s": collective_s,
        "collective_exposed_s": exposed_s,
        "device_ops": device_ops,
        "idle_gaps": idle_gaps[:10],
        "idle_s_by_label": by_label,
        "source": trace.source,
    }
    if steps:
        out["steps"] = steps
        out["busy_s_per_step"] = out["busy_s"] / steps
    return out
