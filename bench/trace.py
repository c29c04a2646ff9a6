"""The one reduction from a profiler trace to what the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three things, all in nanoseconds on the trace's one clock:

  ops      per chip, every event of the device's ``XLA Ops`` line as
           (start, end, op name, opcode, program), with the HLO opcode
           parsed from the event's HLO text and the program (``XLA
           Modules`` event) that contains it;
  modules  per chip, every ``XLA Modules`` event as (start, end, name);
  spans    the benchmark's host spans (``bench.*`` TraceAnnotations).

Busy time is the union of a chip's op intervals; ops that contain other
ops (``while``, ``conditional``, ``call``) count in the union but not in
per-op sums, where their bodies are counted instead.  Everything is
clipped to the ``bench.window`` span.  ``Trace`` round-trips through JSON
(``to_json``/``from_json``), which is how ``bench/testdata`` keeps a
small recorded trace for the tests."""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

CONTAINERS = {"while", "conditional", "call"}
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def parse_op(text: str):
    """(op name, opcode) of an HLO instruction's text, e.g.
    ``%fusion.3 = u32[8]{0} fusion(...)`` -> ("fusion.3", "fusion").
    A name without HLO text comes back as (text, text)."""
    m = re.match(r"%?([^\s=]+) = ", text)
    if not m:
        return text, text
    rest = text[m.end():]
    if rest.startswith("("):              # tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = re.match(r"\s*([\w\-.]+)\(", rest)
    return m.group(1), (op.group(1) if op else "")


def program(module_name: str) -> str:
    """A module's name without its fingerprint: ``jit_f(123)`` ->
    ``jit_f``."""
    return module_name.split("(", 1)[0]


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)      # chip -> [(s, e, name,
    #                                              opcode, program)]
    modules: dict = field(default_factory=dict)  # chip -> [(s, e, name)]
    spans: list = field(default_factory=list)    # [(s, e, name)]

    # ------------------------------------------------------------ json --

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({int(k): [tuple(x) for x in v]
                    for k, v in d["ops"].items()},
                   {int(k): [tuple(x) for x in v]
                    for k, v in d["modules"].items()},
                   [tuple(x) for x in d["spans"]])

    # ---------------------------------------------------------- window --

    def window(self):
        """(start, end) of the ``bench.window`` span."""
        w = [(s, e) for s, e, n in self.spans if n == "bench.window"]
        if len(w) != 1:
            raise ValueError(f"expected one bench.window span, got {len(w)}")
        return w[0]

    def window_s(self) -> float:
        s, e = self.window()
        return (e - s) / 1e9

    def chips(self):
        return sorted(self.ops)

    def _ops(self, chip):
        lo, hi = self.window()
        return [o for o in self.ops[chip] if o[1] > lo and o[0] < hi]

    def busy(self, chip):
        """Merged busy intervals of one chip inside the window."""
        lo, hi = self.window()
        return union(_clip([(o[0], o[1]) for o in self.ops[chip]], lo, hi))

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the chips."""
        chips = self.chips()
        if not chips:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(c))
                   for c in chips) / len(chips) / 1e9

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    # --------------------------------------------------------- op sums --

    def op_seconds(self, keep) -> float:
        """Seconds of the window's ops for which ``keep(name, opcode,
        program)`` holds, leaving out containers, averaged over the
        chips."""
        chips = self.chips()
        if not chips:
            return 0.0
        lo, hi = self.window()
        total = 0
        for c in chips:
            for s, e, name, opcode, prog in self._ops(c):
                if opcode not in CONTAINERS and keep(name, opcode, prog):
                    total += min(e, hi) - max(s, lo)
        return total / len(chips) / 1e9

    def union_seconds(self, keep) -> float:
        """Seconds of the window covered by the ops for which ``keep(name,
        opcode, program)`` holds, containers included, as the union of
        their intervals on each chip, averaged over the chips."""
        chips = self.chips()
        if not chips:
            return 0.0
        lo, hi = self.window()
        return sum(e - s for c in chips for s, e in union(_clip(
            [(o[0], o[1]) for o in self._ops(c) if keep(*o[2:])], lo, hi))
        ) / len(chips) / 1e9

    def module_seconds(self, keep) -> float:
        """Seconds of the window's programs whose name satisfies
        ``keep``, averaged over the chips."""
        chips = self.chips()
        if not chips:
            return 0.0
        lo, hi = self.window()
        return sum(min(e, hi) - max(s, lo)
                   for c in chips for s, e, n in self.modules.get(c, [])
                   if e > lo and s < hi and keep(n)) / len(chips) / 1e9

    def count_spans(self, name: str) -> int:
        """How many spans of ``name`` end inside the window."""
        lo, hi = self.window()
        return sum(1 for s, e, n in self.spans
                   if n == name and lo <= e <= hi)

    def span_seconds(self, name: str):
        """Durations of the spans of ``name`` that end inside the
        window."""
        lo, hi = self.window()
        return [(e - s) / 1e9 for s, e, n in self.spans
                if n == name and lo <= e <= hi]

    # ------------------------------------------------------- breakdown --

    def top_ops(self, k: int = 10):
        """The ``k`` ops that took most device time in the window, as
        [["program:op (opcode)", seconds averaged over chips], ...]."""
        chips = self.chips()
        lo, hi = self.window()
        acc = {}
        for c in chips:
            for s, e, name, opcode, prog in self._ops(c):
                if opcode in CONTAINERS:
                    continue
                key = f"{prog}:{name} ({opcode})"
                acc[key] = acc.get(key, 0) + min(e, hi) - max(s, lo)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / max(len(chips), 1) / 1e9] for n, v in top]

    def idle_gaps(self, k: int = 10):
        """The ``k`` longest stretches of the window in which no chip ran
        an op, each named by the innermost ``bench.*`` host span around
        its middle: [[span name, seconds], ...]."""
        lo, hi = self.window()
        busy = union([iv for c in self.chips() for iv in self.busy(c)])
        gaps, t = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            around = [(ss, ee, n) for ss, ee, n in self.spans
                      if ss <= mid <= ee and n != "bench.window"]
            name = (min(around, key=lambda x: x[1] - x[0])[2]
                    if around else "bench.window")
            out.append([name, (e - s) / 1e9])
        return out


def load(logdir: str, chips: int = None) -> Trace:
    """Reduce the newest ``.xplane.pb`` under ``logdir``.  ``chips``
    keeps the first that many TPU planes (all where None)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    tr = Trace()
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chips is not None and chip >= chips:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            tr.modules[chip] = mods
            ops = []
            j = 0
            for e in sorted(lines["XLA Ops"].events,
                            key=lambda e: e.start_ns) \
                    if "XLA Ops" in lines else []:
                s, en = e.start_ns, e.start_ns + e.duration_ns
                while j < len(mods) and mods[j][1] < s:
                    j += 1
                prog = (program(mods[j][2])
                        if j < len(mods) and mods[j][0] <= s else "")
                name, opcode = parse_op(e.name)
                if opcode == "custom-call" and "tpu_custom_call" in e.name:
                    opcode = "tpu_custom_call"
                ops.append((s, en, name, opcode, prog))
            tr.ops[chip] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        tr.spans.append((e.start_ns,
                                         e.start_ns + e.duration_ns, e.name))
    tr.spans.sort()
    return tr


def read(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
