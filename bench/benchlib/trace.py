"""Profiler capture and the reduction from a trace to numbers.

``capture`` wraps the measured window in ``jax.profiler`` and returns the
``.xplane.pb`` it wrote.  ``load`` turns that file into a small plain
form — device op intervals and host spans, in nanoseconds on one clock —
and every number is computed from that form by the functions below, so a
small trace written out by hand in the tests checks the arithmetic:

  busy            the union of one device's op intervals inside the
                  window, averaged over the devices (``by_device``): the
                  busy time of one chip of the cell's, which ``busy_s``
                  and every busy or idle reading take;
  idle share      1 - busy / window;
  op totals       device seconds of the innermost ops by op name (a TPU
                  trace names an op by its HLO text, ``%fusion.12 = ...``:
                  the instruction name is kept, its numeric suffix
                  dropped, so ``fusion.12`` and ``fusion.40`` add up; an
                  op that holds others, such as a ``while``, is left out
                  so that no time counts twice);
  kernel seconds  device seconds of the events of one kernel, found by the
                  kernel's stable name;
  idle gaps       each stretch of the window in which no op runs, named by
                  the innermost host span open at its midpoint, totalled
                  by that name.

Op totals and idle gaps are a device's; ``mean_totals`` averages them
over the devices, as busy is.  ``device``, every device's ops together,
serves the readings that add work up over the chips, such as a kernel's
seconds.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

Interval = Tuple[int, int, str]          # (start_ns, end_ns, name)

#: the device line that holds one event per executed XLA op
DEVICE_OP_LINE = "XLA Ops"


@contextlib.contextmanager
def capture() -> Iterator[Dict[str, Optional[str]]]:
    """Trace the block; yields a dict whose ``"path"`` is the xplane file
    once the block has ended.  The directory is a temporary one (under
    ``TMPDIR``); ``discard`` removes it.  Python function calls are not
    traced: the reduction reads device ops and the ``TraceAnnotation``
    spans only, and a traced call of every Python function would slow
    the host path it measures and swell the trace."""
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    out: Dict[str, Optional[str]] = {"dir": d, "path": None}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        out["path"] = files[0] if files else None


def discard(cap: Dict[str, Optional[str]]) -> None:
    shutil.rmtree(cap["dir"], ignore_errors=True)


def load(path: str) -> Dict[str, object]:
    """``{"device": [...], "by_device": [[...], ...], "host": [...],
    "meta": {...}}`` from an xplane file: op events of
    every device plane's op line (all planes together, and plane by
    plane), every host thread event (TraceAnnotation spans among them),
    and for each device op name the text of its event stats (the HLO
    op's metadata, such as the ``pallas_call`` a kernel came from, and
    its shapes)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    by_device: List[List[Interval]] = []
    host: List[Interval] = []
    meta: Dict[str, str] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = [ln for ln in plane.lines if ln.name == DEVICE_OP_LINE]
            if lines:
                by_device.append([])
            for line in lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    by_device[-1].append((s, s + int(ev.duration_ns),
                                          ev.name))
                    if ev.name not in meta:
                        meta[ev.name] = " ".join(
                            f"{k}={v}" for k, v in ev.stats)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append((s, s + int(ev.duration_ns), ev.name))
    # a trace with no device plane (a CPU run) reads as one idle device
    by_device = by_device or [[]]
    device = [ev for evs in by_device for ev in evs]
    return {"device": device, "by_device": by_device, "host": host,
            "meta": meta}


def union(intervals: Sequence[Interval], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """The merged intervals, clipped to ``[lo, hi]``."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e, _ in intervals
                   if e > lo and s < hi)
    merged: List[Tuple[int, int]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_ns(device: Sequence[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(device, lo, hi))


def busy_ns_per_device(by_device: Sequence[Sequence[Interval]], lo: int,
                       hi: int) -> float:
    """``busy_ns`` of each device's own ops, averaged over the devices:
    the busy time of one chip of several (one device: ``busy_ns``)."""
    return (sum(busy_ns(evs, lo, hi) for evs in by_device)
            / max(len(by_device), 1))


def mean_totals(per_device: Sequence[Sequence[Tuple[str, float]]]
                ) -> List[Tuple[str, float]]:
    """Totals by name (``op_totals``, ``idle_gaps``) of each device,
    averaged over the devices, largest first."""
    tot: Dict[str, float] = {}
    for rows in per_device:
        for k, v in rows:
            tot[k] = tot.get(k, 0.0) + v
    n = max(len(per_device), 1)
    return sorted(((k, v / n) for k, v in tot.items()), key=lambda kv: -kv[1])


def op_name(name: str) -> str:
    """An op's instruction name without its numeric instance suffix
    (``%while.258 = (...) while(...)`` and ``while.3`` give ``while``)."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[._]\d+$", "", head)


def leaves(device: Sequence[Interval]) -> List[Interval]:
    """The ops that hold no other op: on one device line an op that
    runs others (a ``while``, a ``call``) encloses the next op."""
    evs = sorted(device, key=lambda ev: (ev[0], -ev[1]))
    return [ev for i, ev in enumerate(evs)
            if not (i + 1 < len(evs) and evs[i + 1][0] < ev[1]
                    and evs[i + 1][1] <= ev[1])]


def op_totals(device: Sequence[Interval], lo: int, hi: int
              ) -> List[Tuple[str, float]]:
    """Device seconds by op name inside the window, innermost ops
    only, largest first."""
    tot: Dict[str, int] = {}
    for s, e, name in leaves(device):
        if e > lo and s < hi:
            key = op_name(name)
            tot[key] = tot.get(key, 0) + (min(e, hi) - max(s, lo))
    return sorted(((k, v / 1e9) for k, v in tot.items()),
                  key=lambda kv: -kv[1])


def kernel_events(device: Sequence[Interval], meta: Dict[str, str],
                  kernel: str, lo: int, hi: int) -> List[Interval]:
    """The events of one kernel inside the window, found by its stable
    name in the op's name or stats."""
    return [ev for ev in device
            if (kernel in ev[2] or kernel in meta.get(ev[2], ""))
            and ev[1] > lo and ev[0] < hi]


def idle_gaps(device: Sequence[Interval], host: Sequence[Interval],
              lo: int, hi: int, spans: Sequence[str]
              ) -> List[Tuple[str, float]]:
    """Idle seconds inside the window, totalled by the innermost host
    span (one of ``spans``) open at each gap's midpoint; ``"(none)"``
    where none is."""
    busy = union(device, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    named = sorted((s, e, n) for s, e, n in host if n in spans)
    tot: Dict[str, int] = {}
    active: List[Interval] = []
    j = 0
    for s, e in gaps:                      # midpoints rise with the gaps
        mid = (s + e) // 2
        while j < len(named) and named[j][0] <= mid:
            active.append(named[j])
            j += 1
        active = [h for h in active if h[1] >= mid]
        inner = min(active, key=lambda h: h[1] - h[0], default=None)
        key = inner[2] if inner else "(none)"
        tot[key] = tot.get(key, 0) + (e - s)
    return sorted(((k, v / 1e9) for k, v in tot.items()),
                  key=lambda kv: -kv[1])
