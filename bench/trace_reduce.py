"""Reduce a profiler trace (``.xplane.pb``) to device ops and host spans.

Device ops come from the op line of each TPU device plane, where each event
is named by its HLO instruction's text, and programs from the plane's
program line.  Host spans come from the host threads that
carry the benchmark's own ``bench.*`` annotations.  Both are on the
profiler's one clock, in nanoseconds.  A kernel is an op that ran as a
custom call (a Pallas kernel compiled by Mosaic); a collective is an op of
the collective kinds below; the rest are the ops XLA wrote around them.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

#: the line of a device plane that holds one event per executed op
DEVICE_OP_LINE = "XLA Ops"
#: the line that holds one event per executed program
DEVICE_MODULE_LINE = "XLA Modules"
_COLLECTIVE = re.compile(
    r"\b(collective-permute|all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-broadcast)(-start|-done)?\(")
_KERNEL = re.compile(r"\bcustom-call\(")


def op_name(text: str) -> str:
    """``fusion.3`` from ``%fusion.3 = f32[8]{0} fusion(...)``."""
    return text.lstrip("%").split(" = ", 1)[0].split(" ", 1)[0]


def op_kind(text: str) -> str:
    """"kernel", "collective" or "op", from an op's HLO text."""
    if _KERNEL.search(text):
        return "kernel"
    if _COLLECTIVE.search(text):
        return "collective"
    return "op"


class Op(NamedTuple):
    name: str
    start: int                  # ns
    end: int                    # ns
    kind: str = "op"            # "kernel", "collective" or "op"


def union_ns(intervals) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _merge(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _subtract(a, b) -> int:
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


class Trace:
    def __init__(self, devices: dict[str, list[Op]], spans: list[Op],
                 modules: dict[str, list[Op]] | None = None):
        self.devices = devices          # device plane name -> its ops
        self.spans = spans              # host events of the bench threads
        self.modules = modules or {}    # device plane name -> its programs

    def window(self) -> tuple[int, int]:
        """The benchmark's ``bench.window`` span, else the device ops'
        extent."""
        for s in self.spans:
            if s.name == "bench.window":
                return s.start, s.end
        ops = [o for ops in self.devices.values() for o in ops]
        return min(o.start for o in ops), max(o.end for o in ops)

    def ops(self, lo: int, hi: int, kind: str | None = None,
            name: str | None = None):
        """Per device, its ops inside [lo, hi] (clipped), of ``kind``, and
        named ``name`` where given: ``name`` or XLA's ``name.<n>``."""
        out = {}
        for dev, ops in self.devices.items():
            out[dev] = [o._replace(start=max(o.start, lo), end=min(o.end, hi))
                        for o in ops if o.end > lo and o.start < hi
                        and (kind is None or o.kind == kind)
                        and (name is None or o.name.split(".")[0] == name)]
        return out

    def op_seconds(self, lo: int, hi: int, kind: str | None = None,
                   name: str | None = None) -> float:
        """Summed device time of the ops of ``kind`` (and ``name``),
        averaged over devices.  Ops nested in a loop op are summed with it;
        use ``busy_s`` for the time the device was occupied."""
        per = self.ops(lo, hi, kind, name)
        if not per:
            return 0.0
        return sum(sum(o.end - o.start for o in ops)
                   for ops in per.values()) / len(per) / 1e9

    def module_seconds(self, lo: int, hi: int, prefix: str) -> float:
        """Device time of the programs whose name starts with ``prefix``,
        averaged over devices."""
        if not self.modules:
            return 0.0
        tot = [sum(min(m.end, hi) - max(m.start, lo) for m in mods
                   if m.name.startswith(prefix) and m.end > lo
                   and m.start < hi)
               for mods in self.modules.values()]
        return sum(tot) / len(tot) / 1e9

    def busy_s(self, lo: int, hi: int) -> float:
        """Seconds in which some op ran, averaged over devices."""
        per = self.ops(lo, hi)
        if not per:
            return 0.0
        return sum(union_ns((o.start, o.end) for o in ops)
                   for ops in per.values()) / len(per) / 1e9

    def idle_share(self, lo: int, hi: int) -> float:
        """Per cent of the window in which no op ran on the device."""
        return 100.0 * (1.0 - self.busy_s(lo, hi) / ((hi - lo) / 1e9))

    def exposed_s(self, lo: int, hi: int, kind: str = "collective") -> float:
        """Seconds in which an op of ``kind`` ran and no other op did,
        averaged over devices."""
        per = self.ops(lo, hi)
        if not per:
            return 0.0
        tot = 0
        for ops in per.values():
            mine = _merge((o.start, o.end) for o in ops if o.kind == kind)
            rest = _merge((o.start, o.end) for o in ops if o.kind != kind)
            tot += _subtract(mine, rest)
        return tot / len(per) / 1e9

    def host_label(self, t: int) -> str:
        """The innermost host span running at time ``t``."""
        best = None
        for s in self.spans:
            if s.start <= t < s.end and (best is None
                                          or s.end - s.start
                                          < best.end - best.start):
                best = s
        return best.name if best is not None else "(no host span)"

    def breakdown(self, lo: int, hi: int, top: int = 10) -> dict:
        """The device ops that took most time (summed over devices), and
        the longest idle gaps of the first device with the host span each
        falls in."""
        per = self.ops(lo, hi)
        totals: dict[str, int] = {}
        for ops in per.values():
            for o in ops:
                totals[o.name] = totals.get(o.name, 0) + o.end - o.start
        device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        if per:
            first = per[sorted(per)[0]]
            busy = _merge((o.start, o.end) for o in first)
            cur = lo
            for s, e in busy + [(hi, hi)]:
                if s > cur:
                    gaps.append((s - cur, (s + cur) // 2))
                cur = max(cur, e)
        gaps.sort(key=lambda g: -g[0])
        return {"device_ops": [[n, t / 1e9] for n, t in device_ops],
                "idle_gaps": [[self.host_label(mid), g / 1e9]
                              for g, mid in gaps[:top]]}


def from_profile(pd) -> Trace:
    devices: dict[str, list[Op]] = {}
    modules: dict[str, list[Op]] = {}
    spans: list[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == DEVICE_MODULE_LINE:
                    modules[plane.name] = [
                        Op(e.name.split("(", 1)[0], int(e.start_ns),
                           int(e.start_ns) + int(e.duration_ns))
                        for e in line.events]
                elif line.name == DEVICE_OP_LINE:
                    seen: dict[str, tuple[str, str]] = {}
                    ops = []
                    for e in line.events:
                        text = e.name
                        nk = seen.get(text)
                        if nk is None:
                            nk = seen[text] = (op_name(text), op_kind(text))
                        start = int(e.start_ns)
                        ops.append(Op(nk[0], start,
                                      start + int(e.duration_ns),
                                      kind=nk[1]))
                    devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Op(e.name, int(e.start_ns),
                          int(e.start_ns) + int(e.duration_ns))
                       for e in line.events]
                if any(o.name.startswith("bench.") for o in evs):
                    spans.extend(evs)
    return Trace(devices, spans, modules)


def load(log_dir: str) -> Trace:
    """The trace ``jax.profiler.trace(log_dir)`` wrote."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime)))
