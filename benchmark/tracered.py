"""Reduction of a jax.profiler trace (.xplane.pb) to the device numbers.

What a TPU trace holds (looked at by hand, PR 28): one plane per chip named
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per execution
of a compiled program: a launch), ``XLA Ops`` (one event per operation, by
the HLO's own text) and ``Async XLA Ops`` (copies in flight, overlapping the
others); and a plane ``/host:CPU`` with one line per thread that carries the
``jax.profiler.TraceAnnotation`` events. All on one clock, in nanoseconds.

The harness brackets its traced window with an annotation ``bench:window``
and each request with ``bench:submit:<query>:<request>``; the reduction
clips everything to the window. Busy is the union of the ``XLA Ops``
intervals (the union, so nested and overlapping operations count once),
averaged over the chips that ran anything; launches are the ``XLA Modules``
events that begin inside the window.

``device_ops`` ranks operations by ``<program>/<op>``: the program is the
``XLA Modules`` event that encloses the op (``jit_step``; many programs
have a ``fusion.1``; see ``program_labels``), and every instant of the ``XLA Ops`` line is charged
once, to the op that began last: a ``while``'s body ops lie inside its
interval, so they get their own time and the ``while`` keeps what is left.
The events carry no ``op_name`` or scope (looked at, PR 38), so there is no
ranking by ``jax.named_scope``.

    python benchmark/tracered.py <file.xplane.pb>      # print the reduction
"""

import bisect
import gzip
import json
import re
import sys

WINDOW = "bench:window"
SUBMIT = "bench:submit:"
_OP_NAME = re.compile(r"^%?([A-Za-z0-9_.\-]+)")


def union(intervals: list) -> list:
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_label(name: str) -> str:
    """A device op's event name is its whole HLO line; keep the result's
    name, which is what stays stable between two runs, with the op kind
    where the name does not say it and a custom call's target."""
    m = _OP_NAME.match(name)
    head = m.group(1) if m else name[:40]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    if target:
        return f"{head} {target.group(1)}"
    kind = re.search(r"\}?\s([a-z][a-z0-9\-]*)\(", name)
    if kind and kind.group(1) not in head:
        return f"{head} {kind.group(1)}"
    return head


def program_labels(modules: list) -> dict:
    """{event name: label} for the ``XLA Modules`` events of a line:
    ``jit_step(6844749427986326659)`` -> ``jit_step``. Where several
    programs share a name (every program loaded from the jax.export store is
    ``jit_call``), the first six digits of the module's own number follow
    it: ``jit_call.571651``."""
    ids = {}
    for name in {n for n, _, _ in modules}:
        head, _, rest = name.partition("(")
        ids.setdefault(head, []).append((name, rest.rstrip(")")))
    return {name: head if len(named) == 1 else f"{head}.{num[:6]}"
            for head, named in ids.items() for name, num in named}


def self_times(ops: list, modules: list, lo, hi) -> dict:
    """{``<program>/<op>``: ns} inside [lo, hi]. One sweep over the ops of a
    line in order of their start: an instant belongs to the op that began
    last among those running, so the sum over the labels is the line's
    busy time and no nanosecond is counted twice."""
    programs = program_labels(modules)
    mods = sorted((s, e, programs[n]) for n, s, e in modules)
    starts = [m[0] for m in mods]
    labels, out = {}, {}

    def label(name, s):
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        key = (prog, name)
        if key not in labels:
            labels[key] = f"{prog}/{op_label(name)}"
        return labels[key]

    def charge(lab, a, b):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out[lab] = out.get(lab, 0) + b - a
    stack, cursor = [], lo  # [(label, end)]; all before cursor is charged
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            lab, end = stack.pop()
            charge(lab, cursor, end)
            cursor = max(cursor, end)
        if stack:
            charge(stack[-1][0], cursor, s)
        cursor = max(cursor, s)
        stack.append((label(name, s), e))
    while stack:
        lab, end = stack.pop()
        charge(lab, cursor, end)
        cursor = max(cursor, end)
    return out


def read_planes(path: str) -> dict:
    """{"devices": {plane: {line: [(name, start, end)]}}, "host": [(name,
    start, end)]}: host events are the annotations only (names that start
    with ``bench:``), whatever thread they ran on."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):  # the recorded trace of testdata/ is kept packed
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
            devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"devices": devices, "host": host}


def reduce_planes(planes: dict, spans: list = ()) -> dict:
    """``spans`` are (name, start_ns, end_ns) on the trace's clock: the
    program's own spans, aligned by the caller, used to name idle gaps."""
    windows = [h for h in planes["host"] if h[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW} annotations")
    _, lo, hi = windows[0]
    window_ns = hi - lo
    busy_per_chip, launches, op_ns, merged_all = [], 0, {}, []
    for lines in planes["devices"].values():
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        if not merged:
            continue
        busy_per_chip.append(sum(e - s for s, e in merged))
        merged_all.append(merged)
        launches += sum(1 for _, s, _e in lines.get("XLA Modules", [])
                        if lo <= s < hi)
        # where a trace has no op line the programs stand in: jit_a/jit_a
        charged = self_times(ops, lines.get("XLA Modules", []), lo, hi)
        for lab, ns in charged.items():
            op_ns[lab] = op_ns.get(lab, 0) + ns
    if not busy_per_chip:
        raise ValueError("no operation ran on a device inside the window")
    # idle gaps of the first chip, named by what the host was doing
    submits = [(n, s, e) for n, s, e in planes["host"] if n.startswith(SUBMIT)]
    edges = [[lo, lo]] + merged_all[0] + [[hi, hi]]
    gaps = label_gaps([(a, b) for (_, a), (b, _) in zip(edges, edges[1:])
                       if b > a], spans, submits)
    top = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": window_ns / 1e9,
            "busy_s": sum(busy_per_chip) / len(busy_per_chip) / 1e9,
            "chips_busy": len(busy_per_chip), "launches": launches,
            "device_ops": top(op_ns), "idle_gaps": top(gaps)}


IN_FLIGHT = "request in flight, outside the program's spans"
NO_REQUEST = "no request in flight"


def label_gaps(gaps: list, spans, submits) -> dict:
    """{label: idle ns}. A gap goes to the narrowest program span that
    covers its middle; failing that, to whether any request was in flight.
    One sweep over gaps and spans, both in time order."""
    cands = sorted([(s, e, n, 0) for n, s, e in spans]
                   + [(s, e, IN_FLIGHT, 1) for _, s, e in submits])
    out, active, i = {}, [], 0
    for a, b in sorted(gaps):
        t = (a + b) / 2
        while i < len(cands) and cands[i][0] <= t:
            active.append(cands[i])
            i += 1
        active = [c for c in active if c[1] >= t]
        best = min(active, key=lambda c: (c[3], c[1] - c[0]), default=None)
        lab = best[2] if best else NO_REQUEST
        out[lab] = out.get(lab, 0) + (b - a)
    return out


def reduce_file(path: str, spans: list = ()) -> dict:
    return reduce_planes(read_planes(path), spans)


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
