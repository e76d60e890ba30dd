"""From a profiler trace to the device numbers the metrics read.

The JAX profiler writes an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``; ``jax.profiler.ProfileData`` reads it.
Each device is a plane named ``/device:TPU:<n>``, whose ``XLA Ops`` line
holds one event per operation that ran on it, and its ``XLA Modules`` line
one per program.  The host's threads are the planes named ``/host:...``;
a run traced with the host tracer off has the program's own spans put in
their place (``clock_offset_ns``).

- busy: the union of the op intervals of each device, averaged over the
  devices;
- kernel calls: the op events whose name holds a kernel's name
  (``tree_traverse_`` for the tree walks);
- breakdown: the ops that took most device time, and the longest gaps in
  which no op ran, each named by the host event that best explains it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_PREFIX = "tree_traverse_"
TOP = 10


@dataclass
class DeviceTrace:
    busy_s: float                 # op-covered seconds, averaged over devices
    kernel_calls: list = field(default_factory=list)   # (name, seconds)
    device_ops: list = field(default_factory=list)     # (name, seconds), top
    idle_gaps: list = field(default_factory=list)      # (name, seconds), top


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(line):
    """(name, start_ns, end_ns) of each event; an op's name is cut before
    the HLO text that follows it (``%copy.3 = u32[...] copy(...)``)."""
    return [(ev.name.split(" = ", 1)[0], ev.start_ns, ev.end_ns)
            for ev in line.events]


def read_planes(path: Path):
    """-> ({device plane name: [(name, start_ns, end_ns)]},
           [(name, start_ns, end_ns)] of every host event,
           [(name, start_ns, end_ns)] of every device's XLA modules).
    A ``.gz`` file is read decompressed."""
    import gzip

    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        data = ProfileData.from_serialized_xspace(gzip.decompress(Path(path).read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    devices, host, modules = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            ops = [e for line in plane.lines if line.name == OPS_LINE
                   for e in _events(line)]
            if ops:
                devices[plane.name] = ops
            modules.extend(e for line in plane.lines if line.name == MODULES_LINE
                           for e in _events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return devices, host, modules


def clock_offset_ns(modules: list, mark: str, dispatched_ns: int):
    """profiler ns minus ``perf_counter`` ns: the first run of the module
    named ``mark`` on the device, against ``perf_counter_ns`` at its
    dispatch; None when the trace holds no such run."""
    starts = [s for name, s, _ in modules if name.startswith(mark)]
    return min(starts) - dispatched_ns if starts else None


def merge(intervals) -> np.ndarray:
    """Union of (start, end) intervals, as sorted disjoint rows."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = np.asarray(sorted(intervals), np.float64)
    out = [iv[0].copy()]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.asarray(out)


def _name_gap(gap, host) -> str:
    """The host event that best explains the gap, or ``idle`` when the host
    traced nothing then: the one that fills most of the gap while lying
    mostly inside it (overlap squared over its own length), so a thread's
    enclosing event loses to the call that ran in the gap."""
    s, e = gap
    best, best_key = "idle", 0.0
    for name, hs, he in host:
        ov = min(e, he) - max(s, hs)
        if ov > 0:
            key = ov * ov / max(he - hs, 1)
            if key > best_key:
                best, best_key = name, key
    return best


def reduce(devices: dict, host: list) -> DeviceTrace:
    if not devices:
        raise ValueError("the trace holds no device op: nothing ran on the chip")
    busy, kernels, totals, gaps = [], [], {}, []
    for ops in devices.values():
        merged = merge([(s, e) for _, s, e in ops])
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) / 1e9)
        for name, s, e in ops:
            totals[name] = totals.get(name, 0.0) + (e - s) / 1e9
            if KERNEL_PREFIX in name:
                kernels.append((name, (e - s) / 1e9))
        gaps.extend(zip(merged[:-1, 1], merged[1:, 0]))
    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [(_name_gap(g, host), float(g[1] - g[0]) / 1e9) for g in gaps[:TOP]]
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return DeviceTrace(busy_s=float(np.mean(busy)), kernel_calls=kernels, device_ops=[list(t) for t in top_ops],
                       idle_gaps=[list(t) for t in top_gaps])
