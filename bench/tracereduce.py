"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
HLO op, named by its HLO instruction text (``%neighbor_sample.3 =
s32[...] custom-call(...)``: a Pallas kernel's instruction is named
after its entry point).  The ``XLA Modules`` line holds one span per
executed program (``jit_step(12)``); an op belongs to the module span it
starts in.  Host planes hold the benchmark's own ``TraceAnnotation``
spans, on the same clock.

Everything here works on plain tuples, so a test can feed it a small
recorded trace without a chip.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass(frozen=True)
class Op:
    """One device op: its chip, HLO instruction text, module, start and
    length (ns)."""
    chip: int
    name: str
    module: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def kernel(self) -> str:
        """The instruction's name without its ``%`` and numeric suffix:
        ``%neighbor_sample.3 = ...`` -> ``neighbor_sample``."""
        short = self.name.split(" = ", 1)[0].strip().lstrip("%")
        head, _, tail = short.rpartition(".")
        return head if head and tail.isdigit() else short


def module_name(span_name: str) -> str:
    """``jit_step(12)`` -> ``jit_step``."""
    return span_name.split("(", 1)[0].strip()


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: list          # [Op]
    spans: list        # [Span], host annotations
    chips: int

    def window(self, name: str = "window") -> tuple[float, float]:
        """Bounds of the named host span (the traced window)."""
        found = [s for s in self.spans if s.name == name]
        if not found:
            raise ValueError(f"no {name!r} span in the trace")
        return found[0].start, found[-1].end

    def ops_in(self, lo: float, hi: float) -> list:
        """Ops clipped to [lo, hi]; ops wholly outside are dropped."""
        out = []
        for o in self.ops:
            s, e = max(o.start, lo), min(o.end, hi)
            if e > s:
                out.append(dataclasses.replace(o, start=s, dur=e - s))
        return out


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, chip: int) -> float:
    return sum(e - s for s, e in merge((o.start, o.end) for o in ops
                                       if o.chip == chip))


def idle_gaps(ops, chip: int, lo: float, hi: float) -> list:
    """Gaps (start, end) in [lo, hi] during which ``chip`` ran no op."""
    gaps, t = [], lo
    for s, e in merge((o.start, o.end) for o in ops if o.chip == chip):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def module_at(mods, t: float) -> str:
    """Name of the module span of ``mods`` (sorted (start, end, name))
    that holds time ``t``, or ''."""
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    if i >= 0 and mods[i][0] <= t <= mods[i][1]:
        return mods[i][2]
    return ""


def label_at(spans, t: float, names) -> str:
    """Innermost host span of ``names`` open at time ``t``."""
    best = None
    for s in spans:
        if s.name in names and s.start <= t <= s.end:
            if best is None or s.start >= best.start:
                best = s
    return best.name if best is not None else "other"


def gap_label(spans, lo: float, hi: float, names) -> str:
    """What the host did in the gap [lo, hi): the innermost span of
    ``names`` that covers the most of it."""
    inside = [s for s in spans
              if s.name in names and s.end > lo and s.start < hi]
    cuts = sorted({lo, hi} | {x for s in inside for x in (s.start, s.end)
                              if lo < x < hi})
    held: dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        name = label_at(inside, (a + b) / 2, names)
        held[name] = held.get(name, 0.0) + b - a
    return max(held, key=held.get)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    ops, spans, chips = [], [], set()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            tail = plane.name[len(DEVICE_PREFIX):]
            if not tail.isdigit():
                continue
            chip = int(tail)
            chips.add(chip)
            lines = {line.name: line for line in plane.lines}
            mods = sorted((float(ev.start_ns), float(ev.start_ns)
                           + float(ev.duration_ns), module_name(ev.name))
                          for ev in (lines[MODULES_LINE].events
                                     if MODULES_LINE in lines else ()))
            if OPS_LINE not in lines:
                continue
            for ev in lines[OPS_LINE].events:
                start = float(ev.start_ns)
                ops.append(Op(chip, ev.name, module_at(mods, start), start,
                              float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    spans.append(Span(ev.name, s, s + float(ev.duration_ns)))
    return Trace(ops=ops, spans=spans, chips=max(len(chips), 1))


def summary(tr: Trace, *, step_module,
            labels=("get_batch", "train_step", "train_loop"),
            top: int = 10) -> dict:
    """The traced window's device numbers.

    ``step_module(module) -> bool`` says which ops belong to the train
    step.  Returns busy and window seconds (busy averaged over chips),
    device seconds in and outside the train step (summed over chips),
    and the breakdown: the ops that took most time, and the longest idle
    gaps named by what the host did in most of each."""
    lo, hi = tr.window()
    ops = tr.ops_in(lo, hi)
    chips = sorted({o.chip for o in ops}) or [0]
    busy = sum(busy_ns(ops, c) for c in chips) / len(chips)
    by_op: dict[str, float] = {}
    for o in ops:
        key = f"{o.module}/{o.kernel}"
        by_op[key] = by_op.get(key, 0.0) + o.dur
    gaps = []
    for c in chips:
        for s, e in idle_gaps(ops, c, lo, hi):
            gaps.append((gap_label(tr.spans, s, e, labels), e - s))
    step_ns = sum(o.dur for o in ops if step_module(o.module))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "step_device_s": step_ns / 1e9,
        "prep_device_s": sum(o.dur for o in ops) / 1e9 - step_ns / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gaps, key=lambda kv: -kv[1])[:top]],
    }
