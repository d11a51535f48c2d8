"""Reduction of a profiler trace to intervals, and the interval arithmetic
that the per-layer metric readers share.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes. ``load`` keeps
two things from it: each device's operations (the plane of each TPU
device, its line ``XLA Ops``), and the host annotations of the harness
(``bench.*`` spans on the host's python thread). All times are
nanoseconds on the trace's one clock.

An operation's event carries its HLO instruction's text. ``load`` labels
it ``"<instruction> <opcode>"``, or by the Pallas kernel's name where the
compiled program (``kernel_names``) says that a ``tpu_custom_call``
instruction runs that kernel: the trace itself does not name kernels.
"""
from __future__ import annotations

import base64
import glob
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)     # device -> [(name, t0, t1)]
    spans: list = field(default_factory=list)   # [(name, t0, t1)] host

    def window(self) -> tuple:
        """The traced window: the host span ``bench.window``."""
        w = [(a, b) for n, a, b in self.spans if n == "bench.window"]
        if not w:
            raise ValueError("the trace holds no bench.window span")
        return w[0]


def find(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


_INSTR = re.compile(r"^%?(\S+) = .*?\s([a-z][\w-]*)\(")
_KERNEL = re.compile(rb"(_?[A-Za-z0-9_]*kernel)")


def kernel_names(hlo_text: str) -> dict:
    """{instruction: Pallas kernel name} for the ``tpu_custom_call``
    instructions of a compiled program's HLO text, read from the kernel's
    Mosaic module in the instruction's backend config."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line.strip())
        cfg = line[line.find("backend_config=") + len("backend_config="):]
        try:
            obj, _ = json.JSONDecoder().raw_decode(cfg.strip())
            body = base64.b64decode(obj["custom_call_config"]["body"])
        except (ValueError, KeyError, TypeError):
            continue
        k = _KERNEL.search(body)
        if m and k:
            out[m.group(1)] = k.group(1).decode()
    return out


def label(text: str, kernels: dict) -> str:
    m = _INSTR.match(text)
    if not m:
        return text[:120]
    return kernels.get(m.group(1), f"{m.group(1)} {m.group(2)}")


def load(path: str, kernels: dict | None = None) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    kernels = kernels or {}
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [
                        (label(e.name, kernels), int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name, int(e.start_ns),
                                         int(e.start_ns + e.duration_ns)))
    return tr


def merge(intervals) -> list:
    """Union of [(t0, t1)] as sorted disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def length(intervals) -> int:
    return sum(b - a for a, b in merge(intervals))


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b) -> list:
    """The parts of ``a`` that no interval of ``b`` covers."""
    b = merge(b)
    out = []
    for s, e in merge(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: int, hi: int) -> list:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    return subtract([(lo, hi)], busy)


def is_collective(label: str) -> bool:
    """A collective by its instruction name or opcode."""
    return any(c in label for c in COLLECTIVES)


def busy(trace: Trace, device: str) -> list:
    lo, hi = trace.window()
    return merge(clip([(a, b) for _, a, b in trace.ops[device]], lo, hi))


def op_time(trace: Trace, device: str, match) -> int:
    """Summed duration, clipped to the window, of the device's operations
    whose name ``match`` accepts (nested events counted once)."""
    lo, hi = trace.window()
    return length(clip([(a, b) for n, a, b in trace.ops[device] if match(n)],
                       lo, hi))


def exposed_collective(trace: Trace, device: str) -> int:
    """Time in which a collective runs on the device and nothing else."""
    lo, hi = trace.window()
    ops = [(n, max(a, lo), min(b, hi)) for n, a, b in trace.ops[device]
           if min(b, hi) > max(a, lo)]
    coll = [(a, b) for n, a, b in ops if is_collective(n)]
    other = [(a, b) for n, a, b in ops if not is_collective(n)]
    return length(subtract(coll, other))


def self_times(ops, lo: int, hi: int) -> list:
    """[(name, self ns)] of ops clipped to [lo, hi]: an op's duration less
    the ops nested inside it (a ``while`` holds its body's ops)."""
    rows = sorted(([n, max(a, lo), min(b, hi)] for n, a, b in ops
                   if min(b, hi) > max(a, lo)), key=lambda r: (r[1], -r[2]))
    stack = []
    for r in rows:
        r.append(r[2] - r[1])
        while stack and stack[-1][2] <= r[1]:
            stack.pop()
        if stack:
            stack[-1][3] -= min(r[2], stack[-1][2]) - r[1]
        stack.append(r)
    return [(n, t) for n, _, _, t in rows]


def top_ops(trace: Trace, k: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device self time
    in the window, averaged over devices."""
    lo, hi = trace.window()
    tot: dict = {}
    for ops in trace.ops.values():
        for n, t in self_times(ops, lo, hi):
            tot[n] = tot.get(n, 0) + t
    nd = max(len(trace.ops), 1)
    rows = sorted(tot.items(), key=lambda x: -x[1])[:k]
    return [[n, v / nd / 1e9] for n, v in rows]


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """[[what the host was doing, seconds]] for the longest idle gaps of
    the first device in the window, named by the host span that overlaps
    each gap most."""
    if not trace.ops:
        return []
    lo, hi = trace.window()
    dev = sorted(trace.ops)[0]
    spans = [(n, a, b) for n, a, b in trace.spans if n != "bench.window"]
    out = []
    for a, b in gaps(busy(trace, dev), lo, hi):
        best, over = "host.other", 0
        for n, sa, sb in spans:
            o = min(b, sb) - max(a, sa)
            if o > over:
                best, over = n, o
        out.append([best, (b - a) / 1e9])
    return sorted(out, key=lambda x: -x[1])[:k]
