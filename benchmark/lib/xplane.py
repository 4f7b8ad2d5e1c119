"""Reduction from a profiler trace (`*.xplane.pb`, written by
`jax.profiler` in the process that holds the chip) to the numbers the
per-layer readers use. Kept with the benchmark so that every PR computes
the same number in the same way.

Run as a child (`python benchmark/lib/xplane.py <file> <out.json>`): it
imports jax only to read the file (`jax.profiler.ProfileData`), with
JAX_PLATFORMS=cpu set by the caller, after the server has gone. The
harness process itself never imports jax.

What is read:
- device planes: `/device:TPU:<n>`. Busy is the union of the intervals
  of the events on the plane's `XLA Ops` and `Async XLA Ops` lines (the
  operations and the asynchronous copies that ran on the device); the
  window is the capture's own, from the first to the last event of the
  device's and the runtime's tracer on any plane, and idle share is
  1 - busy / window. The Python tracer's frames (`$file:line name`) do
  not count for it: it starts before the device's tracer and stops
  after it (0.66 s against 0.17 s in the recorded trace of the tests),
  and in that time no device operation would be seen.
- per-program time: the summed durations on the `XLA Modules` line,
  by module name (`jit_program`, `jit_prelude`, ...), with the ops of
  each module from the `XLA Ops` line.
- host planes: the Python frames the profiler's tracer recorded, used
  to say what the server's threads were doing in the longest idle gaps.
"""

from __future__ import annotations

import json
import re
import sys

_WAITING = ("wait", "acquire", "select", "sleep", "recv", "accept", "poll",
            "readinto", "_bootstrap", "run", "serve_forever", "get",
            "handle", "process_request", "finish_request", "__init__",
            "handle_one_request", "epoll", "start_trace", "stop_trace",
            "setprofile")
_MODULE_ID = re.compile(r"\(\d+\)$")
_SAFE = re.compile(r"[^A-Za-z0-9_.\-]+")


def union_length(intervals: list) -> tuple[float, list]:
    """Total length of the union of [start, end) intervals, and the
    merged intervals in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def module_name(event_name: str) -> str:
    return _MODULE_ID.sub("", event_name.strip())


def op_label(event_name: str) -> str:
    """`%fusion.2 = s32[9,4680]{1,0} fusion(...)` -> `fusion.2_s32_9_4680_`;
    a plain `fusion.2` stays."""
    name = event_name.strip().lstrip("%")
    head, sep, rest = name.partition(" = ")
    if not sep:
        return _SAFE.sub("_", head)[:60]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return (_SAFE.sub("_", head) + "_" + _SAFE.sub("_", shape))[:60]


def reduce_planes(planes: list) -> dict:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns, module)]}]}] -> the reduced trace (see module docstring).
    Pure: the tests drive it with hand-made planes too."""
    device = [p for p in planes if p["name"].startswith("/device:TPU:")]
    host = [p for p in planes if p["name"].startswith("/host:")
            and p["name"] != "/host:metadata"]
    all_start, all_end = [], []
    for p in planes:
        for ln in p["lines"]:
            for n, s, d, _m in ln["events"]:
                if not n.startswith("$"):
                    all_start.append(s)
                    all_end.append(s + d)
    if not all_start:
        return {"device_planes": 0}
    t_lo, t_hi = min(all_start), max(all_end)
    out = {"device_planes": len(device), "window_s": (t_hi - t_lo) / 1e9}
    if not device:
        return out
    busy_each, programs, ops, merged_first = [], {}, {}, None
    for p in device:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        op_events = lines.get("XLA Ops") or []
        mod_events = lines.get("XLA Modules") or []
        # an asynchronous copy runs on the device from its start to its
        # done: those spans count as busy beside the operations proper
        running = (op_events or mod_events) + (
            lines.get("Async XLA Ops") or [])
        busy, merged = union_length(
            [(s, s + d) for _n, s, d, _m in running])
        busy_each.append(busy)
        if merged_first is None:
            merged_first = merged
        for n, _s, d, _m in mod_events:
            key = module_name(n)
            rec = programs.setdefault(key, {"seconds": 0.0, "calls": 0})
            rec["seconds"] += d / 1e9
            rec["calls"] += 1
        # an op belongs to the module whose event encloses it
        mods = sorted((s, s + d, module_name(n))
                      for n, s, d, _m in mod_events)
        for n, s, d, m in op_events:
            mod = m or _enclosing(mods, s)
            key = f"{mod}/{op_label(n)}" if mod else op_label(n)
            ops[key] = ops.get(key, 0.0) + d / 1e9
    n_dev = len(device)
    out["busy_s"] = sum(busy_each) / n_dev / 1e9
    out["programs"] = {
        k: {"seconds": v["seconds"] / n_dev, "calls": v["calls"] // n_dev}
        for k, v in programs.items()}
    out["device_ops"] = sorted(
        ([k, v / n_dev] for k, v in ops.items()),
        key=lambda kv: -kv[1])[:10]
    out["idle_gaps"] = _idle_gaps(merged_first or [], host)
    return out


def _enclosing(mods: list, t: float) -> str:
    lo, hi = 0, len(mods)
    while lo < hi:
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][1] >= t:
        return mods[lo - 1][2]
    return ""


def _idle_gaps(merged: list, host: list) -> list:
    """The ten longest gaps between device operations, each named after
    the deepest Python frame that was running (not waiting) at the
    gap's midpoint on the server's host threads. Only gaps BETWEEN two
    operations count: before the first and after the last the profiler
    itself is starting and stopping."""
    gaps = []
    prev = None
    for s, e in merged:
        if prev is not None and s > prev:
            gaps.append((s - prev, prev, s))
        prev = e if prev is None else max(prev, e)
    gaps.sort(reverse=True)
    threads = list(_frames(host))
    out = []
    for length, g0, g1 in gaps[:10]:
        mid = (g0 + g1) / 2
        best = None
        for evs in threads:
            frame = _deepest(evs, mid)
            if frame and (best is None or frame[0] > best[0]):
                best = frame
        label = "host:" + _SAFE.sub("_", best[2].lstrip("$"))[:48] \
            if best else "host:idle"
        out.append([label, length / 1e9])
    return out


def _frames(host: list):
    """The Python frames of each host thread: [(start, end, name)] in
    order of start, an enclosing frame before the ones it encloses."""
    for p in host:
        for ln in p["lines"]:
            evs = sorted(((s, s + d, n) for n, s, d, _m in ln["events"]
                          if n.startswith("$") and d > 0),
                         key=lambda e: (e[0], -e[1]))
            if evs:
                yield evs


def _waits(name: str) -> bool:
    fn = name.rsplit(" ", 1)[-1]
    return fn in _WAITING or fn.startswith("_wait")


def _deepest(evs: list, t: float):
    """The innermost working frame that covers t on one thread."""
    lo, hi = 0, len(evs)
    while lo < hi:
        mid = (lo + hi) // 2
        if evs[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    # walk back over the frames that started before t: the first one
    # that still covers t is the innermost
    for k in range(lo - 1, max(lo - 4000, -1), -1):
        s, e, n = evs[k]
        if e >= t:
            return None if _waits(n) else (s, e, n)
    return None


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for pl in data.planes:
        lines = []
        keep_stats = pl.name.startswith("/device:")
        for ln in pl.lines:
            events = []
            for e in ln.events:
                mod = ""
                if keep_stats and ln.name == "XLA Ops":
                    for k, v in e.stats:
                        if k == "hlo_module":
                            mod = str(v)
                            break
                events.append((e.name, float(e.start_ns),
                               float(e.duration_ns), mod))
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def main(argv) -> int:
    path, out_path = argv[1], argv[2]
    doc = reduce_planes(read_planes(path))
    with open(out_path, "w") as f:
        json.dump(doc, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
