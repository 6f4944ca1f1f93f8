"""Reduce a JAX profiler trace (``.xplane.pb``) to device facts.

From the device planes (``/device:TPU:<n>``) it takes two lines: the
program executions (``XLA Modules``) and the operations inside them
(``XLA Ops``).  It returns, per device plane:

  window_ns     the span of the trace (first to last event on any plane)
  busy_ns       the union of the operation intervals
  gaps          the idle intervals between them, longest first
  modules       {module name: total device ns}
  kernels       {module name: ns in its Mosaic kernel calls}
  ops           {"<program>:<op>": total device ns}, container ops
                (while, conditional, call) left out

On a TPU an op event is named by its HLO text, ``%name.N = <shape>
op(...)``; ``<op>`` is ``name`` without the ``%`` and the ``.N``, and
``<program>`` the module name without ``jit_`` and the program id.  A
Mosaic (Pallas) kernel is an op whose text, ``hlo_category``,
``long_name`` or ``tf_op`` names a custom call.  An op belongs to the
module whose execution interval holds its start.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
KERNEL_MARKS = (" custom-call(", "tpu_custom_call")
CONTAINERS = ("while", "conditional", "call")


def short_op(text: str) -> str:
    """``%paged_decode_attention.5 = bf16[...] custom-call(...)`` ->
    ``paged_decode_attention``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def short_module(name: str) -> str:
    """``jit__decode_paged(5420030503712951001)`` -> ``_decode_paged``."""
    return re.sub(r"\(\d+\)$", "", name).removeprefix("jit_")


@dataclasses.dataclass
class DeviceFacts:
    name: str
    window_ns: Tuple[int, int]
    busy_ns: int
    gaps: List[Tuple[int, int]]            # (start_ns, length_ns)
    modules: Dict[str, int]
    kernels: Dict[str, int]
    ops: Dict[str, int]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(ev) -> Dict[str, object]:
    out = {}
    for st in ev.stats:
        try:
            k, v = st
        except (TypeError, ValueError):
            continue
        out[str(k)] = v
    return out


def _is_kernel(name: str, stats: Dict[str, object]) -> bool:
    if stats.get("hlo_category") == "custom-call":
        return True
    text = " ".join([name] + [str(stats.get(k, "")) for k in
                              ("long_name", "tf_op")])
    return any(m in text for m in KERNEL_MARKS)


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge (start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_planes(planes) -> List[DeviceFacts]:
    """``planes``: the ``planes`` of a ``jax.profiler.ProfileData``."""
    t_lo, t_hi = None, None
    devices = []
    for p in planes:
        for line in p.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                t_lo = s if t_lo is None else min(t_lo, s)
                t_hi = e if t_hi is None else max(t_hi, e)
        if p.name.startswith("/device:TPU:") and "SparseCore" not in p.name:
            devices.append(p)
    out = []
    for p in devices:
        mods, ops = [], []
        for line in p.lines:
            if line.name == MODULE_LINE:
                mods = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
            elif line.name == OP_LINE:
                ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                        _stats(ev)) for ev in line.events]
        mods.sort()
        starts = [m[0] for m in mods]
        modules: Dict[str, int] = collections.Counter()
        kernels: Dict[str, int] = collections.Counter()
        op_ns: Dict[str, int] = collections.Counter()
        for s, e, name in mods:
            modules[name] += e - s
        for s, e, name, st in ops:
            op = short_op(name)
            if op in CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else None
            op_ns[f"{short_module(mod or '')}:{op}"] += e - s
            if mod is not None and _is_kernel(name, st):
                kernels[mod] += e - s
        busy = union([(s, e) for s, e, _, _ in ops])
        gaps, prev = [], t_lo
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s - prev))
            prev = max(prev, e)
        if t_hi is not None and t_hi > prev:
            gaps.append((prev, t_hi - prev))
        gaps.sort(key=lambda g: -g[1])
        out.append(DeviceFacts(
            name=p.name, window_ns=(t_lo or 0, t_hi or 0),
            busy_ns=sum(e - s for s, e in busy), gaps=gaps,
            modules=dict(modules), kernels=dict(kernels), ops=dict(op_ns)))
    return out


def load(path: str) -> List[DeviceFacts]:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def host_events(path: str, names) -> List[Tuple[str, int, int]]:
    """(name, start_ns, end_ns) of host events whose name starts with one
    of ``names`` (the benchmark's own annotations)."""
    from jax.profiler import ProfileData
    out = []
    for p in ProfileData.from_file(path).planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(tuple(names)):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out

