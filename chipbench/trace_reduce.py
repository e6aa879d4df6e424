"""Reduce a profiler trace (``.xplane.pb``) of a window to per-call device
times, device busy time and the breakdown.

What one trace of the engine, read by hand, shows (TPU v5e):

* Each chip is a plane ``/device:TPU:<id>``. Its line ``XLA Modules``
  holds one event per program run, named ``jit_<function>(<program
  fingerprint>)``; its line ``XLA Ops`` holds every operation, nested ops
  included, named by their HLO text (``%while.56 = ...``).
* Both engine programs are jitted functions called ``run``
  (``InferenceEngine._prefill_fn`` and ``_fused_fn``), so their modules
  are both ``jit_run(...)``. ``InferenceEngine.generate`` runs the prefill
  and then the fused decode, so within one engine call the first is the
  prefill. (Counting ``%while`` ops does not tell them apart: the fused
  decode loops over steps and layers, but at prompts over 512 tokens the
  prefill's attention loops over query chunks inside its loop over
  layers too.)
* The small programs between them (``jit__argmax``, ``jit_concatenate``,
  conversions) are the eager operations of ``InferenceEngine.generate``.
* The host's clock and the chip's differ by about a millisecond in the
  trace, so a program is given to the engine call (the host span
  ``chipbench.generate.<index>`` that the harness writes) whose span
  holds the program's midpoint, or else lies within a few milliseconds of
  it.
"""
from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

PREFILL, DECODE = "prefill", "fused_decode"
_DEVICE = re.compile(r"/device:TPU:(\d+)$")
_ENGINE_MODULE = "jit_run("
#: Slack on each side of a host span when a device program is matched to
#: it: the clocks' offset in the trace, with room.
MATCH_SLACK_NS = 3e6


def _merge(starts: np.ndarray, ends: np.ndarray):
    """Union of intervals → (starts, ends) of the disjoint pieces, sorted."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(len(s), dtype=bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    return s[first], np.maximum.reduceat(e, first)


def read(path: str, span_prefix: str) -> Dict:
    """Host spans whose names start with ``span_prefix``, and per device
    its program runs and op intervals, from one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: Dict[str, Tuple[float, float]] = {}
    devices: Dict[int, Dict] = {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
        elif m:
            dev = {"modules": [], "op_starts": [], "op_ends": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns)
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        dev["op_starts"].append(e.start_ns)
                        dev["op_ends"].append(e.start_ns + e.duration_ns)
            devices[int(m.group(1))] = dev
    return {"spans": spans, "devices": devices}


def reduce(path: str, call_prefix: str, window_span: str,
           call_meta: Dict[int, Tuple[int, int]],
           devices_used: List[int]) -> Optional[Dict]:
    """Per engine call its programs' device seconds, the chips' busy
    seconds in the traced window (mean over the chips used), the window's
    seconds, and the breakdown.

    Engine calls are the host spans ``<call_prefix><index>``; the window
    is the host span ``window_span``. ``call_meta`` maps each call index to
    (device id, batch bucket). Returns None where the trace holds no
    window or no device plane of the chips used.
    """
    raw = read(path, os.path.commonprefix([call_prefix, window_span]))
    window = raw["spans"].get(window_span)
    spans = {int(k[len(call_prefix):]): v for k, v in raw["spans"].items()
             if k.startswith(call_prefix)}
    used = sorted(devices_used)
    if window is None or not any(d in raw["devices"] for d in used):
        return None
    w0, w1 = window
    calls: Dict[int, Dict[str, float]] = {}
    busy: List[float] = []
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for dev_id in used:
        dev = raw["devices"].get(dev_id)
        if dev is None:
            busy.append(0.0)
            continue
        mine = {i: s for i, s in spans.items()
                if i in call_meta and call_meta[i][0] == dev_id}
        runs: Dict[int, List[Tuple[float, float]]] = {}
        for name, start, end in dev["modules"]:
            call = _owner(mine, 0.5 * (start + end))
            if name.startswith(_ENGINE_MODULE) and call is not None:
                runs.setdefault(call, []).append((start, end))
                continue
            label = name.split("(")[0]
            if call is not None:
                calls.setdefault(call, _empty())["other"] += (end - start) * 1e-9
            if w0 <= 0.5 * (start + end) <= w1:
                ops[label] = ops.get(label, 0.0) + (end - start) * 1e-9
        for call, programs in runs.items():
            # generate runs the prefill and then the fused decode
            for kind, (start, end) in zip((PREFILL, DECODE), sorted(programs)):
                calls.setdefault(call, _empty())[kind] += (end - start) * 1e-9
                if w0 <= 0.5 * (start + end) <= w1:
                    label = f"{kind} b={call_meta[call][1]}"
                    ops[label] = ops.get(label, 0.0) + (end - start) * 1e-9
        m_start, m_end = _merge(np.asarray(dev["op_starts"], dtype=np.float64),
                                np.asarray(dev["op_ends"], dtype=np.float64))
        m_start, m_end = np.clip(m_start, w0, w1), np.clip(m_end, w0, w1)
        busy.append(float(np.sum(m_end - m_start)) * 1e-9)
        edges = np.concatenate([[w0], m_end]), np.concatenate([m_start, [w1]])
        lengths = np.maximum(edges[1] - edges[0], 0.0)
        for i in np.argsort(-lengths)[:10]:
            mid = 0.5 * (edges[0][i] + edges[1][i])
            inside = _owner(mine, mid) is not None
            gaps.append(("inside_generate" if inside else "between_calls",
                         float(lengths[i]) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "calls": calls,
        "busy_s": float(np.mean(busy)),
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[k, v] for k, v in gaps[:10]],
    }


def _empty() -> Dict[str, float]:
    return {PREFILL: 0.0, DECODE: 0.0, "other": 0.0}


def _owner(spans: Dict[int, Tuple[float, float]], t: float) -> Optional[int]:
    """The call whose span holds time ``t``; where none does, the one whose
    span lies nearest, if within the slack."""
    best, dist = None, MATCH_SLACK_NS
    for idx, (s, e) in spans.items():
        d = max(s - t, t - e, 0.0)
        if d <= dist:
            if d == 0.0:
                return idx
            best, dist = idx, d
    return best
