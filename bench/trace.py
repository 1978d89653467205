"""From the profiler's trace to numbers: device busy time, per-op device time
and idle gaps labeled by what the pane loop was doing.

The window is the load generator's ``bench.window`` span (host clock, in the
trace's own time base).  A device's busy time is the union of its ``XLA Ops``
events inside the window; its idle gaps are the rest.  Each idle gap of the
first device is labeled by the innermost host span that covers its middle on
the pane loop's thread (the thread of the ``session.step`` spans), or as
``loadgen.sleep`` when the loop held nothing and the generator was waiting
for the next pane to fall due.
"""

from __future__ import annotations

import dataclasses
import glob
import re
import shutil

import numpy as np

OPS_LINE = "XLA Ops"
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")


def op_name(hlo: str) -> str:
    """``%sort.1 = s32[200000]{0:T(1024)} sort(...)`` -> ``sort.1 s32[200000]``:
    the TPU trace names each op by its whole HLO instruction; keep the
    instruction's name and result type, without layouts."""
    head, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo[:120]
    out = _LAYOUT.sub("", rest)
    depth = 0
    for i, ch in enumerate(out):
        depth += ch in "([" and 1 or 0
        depth -= ch in ")]" and 1 or 0
        if ch == " " and depth == 0:
            out = out[:i]
            break
    return f"{head.lstrip('%')} {out}"[:120]


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans only; no per-call Python tracing
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop_and_reduce(log_dir: str, chips: int) -> "Reduced":
    import jax

    jax.profiler.stop_trace()
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}; found {files}")
    try:
        return reduce(jax.profiler.ProfileData.from_file(files[0]), chips)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over the chips
    panes: int  # session.step spans begun inside the window
    op_s: dict  # op name -> seconds inside the window, mean over the chips
    gaps: dict  # host label -> idle seconds of the first chip

    def op_ms_per_pane(self, *kinds: str) -> float | None:
        """Device ms per pane of the ops whose instruction name starts with
        one of ``kinds`` (``sort`` takes ``sort.1``, not a fusion fed by
        it); None where no such op ran."""
        total = sum(s for name, s in self.op_s.items() if name.startswith(kinds))
        if total <= 0.0 or self.panes == 0:
            return None
        return total * 1e3 / self.panes

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gaps)}


def _events(line):
    ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
    ev.sort(key=lambda x: (x[1], -x[2]))
    return ev


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals (any order) into disjoint sorted ones."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def _label_points(events, points):
    """For each sorted point, the names of the spans of one thread (properly
    nested) that cover it, outermost first."""
    out, stack, i = [], [], 0
    for t in points:
        while i < len(events) and events[i][1] <= t:
            while stack and stack[-1][2] <= events[i][1]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append([ev[0] for ev in stack])
    return out


def reduce(pd, chips: int) -> Reduced:
    host_lines = [ln for pl in pd.planes if pl.name.startswith("/host") for ln in pl.lines]
    window = loop = gen = None
    for ln in host_lines:
        ev = _events(ln)
        names = {e[0] for e in ev}
        if "bench.window" in names:
            window = next(e for e in ev if e[0] == "bench.window")
            gen = ev
        if "session.step" in names:
            loop = ev
    if window is None:
        raise RuntimeError("trace holds no bench.window span")
    ws, we = window[1], window[2]
    devices = sorted(
        (pl for pl in pd.planes if pl.name.startswith("/device:TPU:")),
        key=lambda pl: int(pl.name.rsplit(":", 1)[1]),
    )[:chips]
    if len(devices) < chips:
        raise RuntimeError(f"trace holds {len(devices)} TPU planes; the cell uses {chips}")
    busy, op_s, gaps = 0.0, {}, {}
    for d, plane in enumerate(devices):
        ops = [(op_name(e[0]), e[1], e[2])
               for ln in plane.lines if ln.name == OPS_LINE for e in _events(ln)]
        s = np.clip(np.array([e[1] for e in ops], float), ws, we)
        e = np.clip(np.array([e[2] for e in ops], float), ws, we)
        for (name, _, _), a, b in zip(ops, s, e):
            if b > a:
                op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9 / chips
        us, ue = union(s[e > s], e[e > s])
        busy += float(np.sum(ue - us)) * 1e-9 / chips
        if d == 0:
            lo = np.concatenate([[ws], ue])
            hi = np.concatenate([us, [we]])
            keep = hi > lo
            lo, hi = lo[keep], hi[keep]
            mids = (lo + hi) / 2
            on_loop = _label_points(loop or [], mids)
            on_gen = _label_points(gen or [], mids)
            for a, b, lp, gp in zip(lo, hi, on_loop, on_gen):
                if "session.step" in lp:
                    inner = lp[-1]
                    label = "session.step" if inner == "session.step" else f"session.step/{inner}"
                elif lp:
                    label = lp[-1]
                elif "loadgen.sleep" in gp:
                    label = "loadgen.sleep"
                else:
                    label = "pane loop outside session.step"
                gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    panes = sum(1 for ev in (loop or []) if ev[0] == "session.step" and ws <= ev[1] < we)
    return Reduced((we - ws) * 1e-9, busy, panes, op_s, gaps)


# -- trimming a recorded trace for the tests -----------------------------------------


def to_text_proto(pd, t_lo_ns: float, t_hi_ns: float, chips: int) -> str:
    """The events of ``[t_lo_ns, t_hi_ns]``, clipped to it, on the first
    ``chips`` TPU planes' op lines and on the host threads that hold the
    harness's spans, as an XSpace text proto that
    ``ProfileData.from_text_proto`` reads."""
    spans = {"bench.window", "session.step", "loadgen.sleep", "sink.wait"}
    out = []
    pid = 0
    for pl in pd.planes:
        if pl.name.startswith("/device:TPU:"):
            if int(pl.name.rsplit(":", 1)[1]) >= chips:
                continue
            lines = [ln for ln in pl.lines if ln.name == OPS_LINE]
        elif pl.name.startswith("/host"):
            lines = [ln for ln in pl.lines if spans & {e.name for e in ln.events}]
        else:
            continue
        pid += 1
        meta: dict[str, int] = {}
        body = []
        for lid, ln in enumerate(lines, 1):
            evs = []
            for e in ln.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if b <= t_lo_ns or a >= t_hi_ns:
                    continue
                a, b = max(a, t_lo_ns), min(b, t_hi_ns)
                mid = meta.setdefault(e.name.replace("\\", "\\\\").replace('"', '\\"'),
                                      len(meta) + 1)
                evs.append(
                    f"    events {{ metadata_id: {mid} offset_ps: {round((a - t_lo_ns) * 1000)} "
                    f"duration_ps: {round((b - a) * 1000)} }}"
                )
            body.append(f'  lines {{\n    id: {lid}\n    name: "{ln.name}"\n'
                        f"    timestamp_ns: {int(t_lo_ns)}\n" + "\n".join(evs) + "\n  }")
        md = "\n".join(
            f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in meta.items()
        )
        out.append(f'planes {{\n  id: {pid}\n  name: "{pl.name}"\n' + "\n".join(body)
                   + "\n" + md + "\n}")
    return "\n".join(out) + "\n"
