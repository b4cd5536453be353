"""Profiler trace of a steady stretch of the window, and its reduction.

``Stretch`` records a JAX profiler trace; ``Trace.from_xplane`` keeps what
the metric readers use, in a compact form that ``Trace.save``/``load``
write as JSON (the self-checks keep a small recorded one):

* each device plane's ``XLA Ops`` and ``XLA Modules`` lines, as
  ``[name, start_ns, duration_ns]`` events;
* the benchmark's own host spans (``TraceAnnotation`` names starting with
  ``cb.``), among them ``cb.stretch`` around the traced stretch.

Busy time is the union of the op intervals on a device inside the
stretch; everything else is idle.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import tempfile

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = ("XLA Ops", "XLA Modules")
SPAN_PREFIX = "cb."
STRETCH = "cb.stretch"
SUBMIT = "cb.submit"
NAME_CHARS = 160       # an op's HLO text is kept to its name and shape
TICK_SLACK = 3          # ticks in flight at either end of the stretch
DOMINANCE = 2.0         # execute's device time over any other per-tick one


class Stretch:
    """Traces a stretch of the window driven by the load loop: from the
    first tick at or after ``START_SHARE`` of the window, for
    ``EVAC_ROUNDS`` evacuation periods of ticks, so that every stretch
    holds the same whole rounds of maintenance (cut at ``END_SHARE`` of
    the window, should the ticks come slower).  ``snapshot()`` returns the
    plane counters as they stand after the last submitted tick (device
    arrays, not waited for); ``counters`` gives their change over the
    stretch's ticks once the window has closed."""

    START_SHARE = 0.25
    END_SHARE = 0.9
    EVAC_ROUNDS = 2

    def __init__(self, seconds: float, evac_every: int, snapshot):
        self.a, self.b = self.START_SHARE * seconds, self.END_SHARE * seconds
        self.ticks = self.EVAC_ROUNDS * evac_every
        self.snapshot = snapshot
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.span = None
        self.tick0 = self.tick1 = None
        self.snaps = []

    def __call__(self, t: float, ticks: int):
        import jax
        if self.tick0 is None and self.a <= t < self.b:
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation(STRETCH)
            self.span.__enter__()
            self.tick0 = ticks
            self.snaps.append(self.snapshot())
        elif self.span is not None and (ticks - self.tick0 >= self.ticks
                                        or t >= self.b):
            self.tick1 = ticks
            self.snaps.append(self.snapshot())
            self.close()

    def close(self):
        import jax
        if self.span is not None:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.span = None

    def counters(self):
        """``(change of each plane counter, summed over shards; ticks)``
        over the stretch, or None where it did not close on a tick."""
        import jax
        if len(self.snaps) != 2:
            return None
        a, b = (jax.device_get(s)._asdict() for s in self.snaps)
        return ({k: int(np.sum(b[k])) - int(np.sum(a[k])) for k in a},
                self.tick1 - self.tick0)

    def read(self) -> "Trace":
        try:
            return Trace.from_logdir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _union(iv: np.ndarray) -> float:
    """Total length of the union of ``[start, end]`` rows."""
    if len(iv) == 0:
        return 0.0
    iv = iv[np.argsort(iv[:, 0])]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + cur_e - cur_s


class Trace:
    def __init__(self, devices: dict, spans: list):
        # devices: {index: {line: [[name, start_ns, dur_ns], ...]}}
        self.devices = {int(k): v for k, v in devices.items()}
        self.spans = spans          # [[name, start_ns, dur_ns], ...]
        st = [s for s in spans if s[0] == STRETCH]
        if st:
            self.t0, self.t1 = st[0][1], st[0][1] + st[0][2]
        else:
            ends = [e[1] + e[2] for d in self.devices.values()
                    for ev in d.values() for e in ev]
            starts = [e[1] for d in self.devices.values()
                      for ev in d.values() for e in ev]
            self.t0, self.t1 = (min(starts), max(ends)) if starts else (0, 0)

    # -- building ----------------------------------------------------------

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        devices, spans = {}, []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name in LINES:
                    devices.setdefault(int(m.group(1)), {})[line.name] = [
                        [e.name[:NAME_CHARS], float(e.start_ns),
                         float(e.duration_ns)]
                        for e in line.events]
                elif not m and plane.name.startswith("/host"):
                    spans += [[e.name, float(e.start_ns),
                               float(e.duration_ns)]
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        return cls(devices, spans)

    @classmethod
    def from_logdir(cls, logdir: str) -> "Trace":
        found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {logdir}")
        return cls.from_xplane(found[0])

    def save(self, path: str):
        with gzip.open(path, "wt") as f:
            json.dump({"devices": self.devices, "spans": self.spans}, f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(d["devices"], d["spans"])

    # -- reductions ----------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def events(self, dev: int, line: str) -> list:
        """Events of ``line`` on device ``dev`` that start in the stretch."""
        return [e for e in self.devices.get(dev, {}).get(line, [])
                if self.t0 <= e[1] < self.t1]

    def _intervals(self, dev: int) -> np.ndarray:
        ev = self.devices.get(dev, {}).get("XLA Ops") or \
            self.devices.get(dev, {}).get("XLA Modules", [])
        iv = np.array([[e[1], e[1] + e[2]] for e in ev], np.float64)
        if len(iv) == 0:
            return iv.reshape(0, 2)
        iv = np.clip(iv, self.t0, self.t1)
        return iv[iv[:, 1] > iv[:, 0]]

    def busy_s(self, dev: int) -> float:
        return _union(self._intervals(dev)) * 1e-9

    def mean_busy_s(self) -> float:
        devs = sorted(self.devices)
        return float(np.mean([self.busy_s(d) for d in devs])) if devs else 0.0

    def submits(self) -> int:
        """Ticks the host submitted in the stretch (``cb.submit`` spans)."""
        return sum(1 for s in self.spans
                   if s[0] == SUBMIT and self.t0 <= s[1] < self.t1)

    def roles(self, dev: int) -> dict:
        """Role of each program on ``dev`` in the stretch.  The engine's
        jitted programs carry no names of their own (every one is
        ``jit__unknown(<id>)``), so roles come from how often they run:
        programs that run about once a tick are the tick's own, the one of
        them with the most device time is ``exec`` and the others ``plan``;
        programs that run less often are ``maint``.  Raises where that
        reading does not hold: the ``exec`` program must run once for each
        tick the host submitted (give or take ``TICK_SLACK`` in flight at
        the stretch's ends) and take ``DOMINANCE`` times the device time
        of any other per-tick program."""
        count: dict = {}
        total: dict = {}
        for name, _, dur in self.events(dev, "XLA Modules"):
            count[name] = count.get(name, 0) + 1
            total[name] = total.get(name, 0.0) + dur
        if not count:
            return {}
        top = max(count.values())
        tick = sorted((n for n in count if count[n] >= top / 2),
                      key=lambda n: -total[n])
        ex = tick[0]
        n_sub = self.submits()
        if n_sub and abs(count[ex] - n_sub) > TICK_SLACK:
            raise ValueError(
                f"device {dev}: the execute program ran {count[ex]} times "
                f"for {n_sub} submitted ticks; the programs are not one "
                f"plan and one execute a tick")
        if len(tick) > 1 and total[tick[1]] * DOMINANCE > total[ex]:
            raise ValueError(
                f"device {dev}: no per-tick program dominates "
                f"({total[ex] * 1e-9:.4f} s against "
                f"{total[tick[1]] * 1e-9:.4f} s); the execute program "
                f"cannot be told from the others")
        return {n: "exec" if n == ex else "plan" if n in tick else "maint"
                for n in count}

    def role_s(self, dev: int, role: str) -> float:
        """Device seconds of the programs with ``role``."""
        r = self.roles(dev)
        return sum(e[2] for e in self.events(dev, "XLA Modules")
                   if r.get(e[0]) == role) * 1e-9

    def role_count(self, dev: int, role: str) -> int:
        r = self.roles(dev)
        return sum(1 for e in self.events(dev, "XLA Modules")
                   if r.get(e[0]) == role)

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` device ops that took most time, seconds averaged over
        the devices."""
        tot: dict = {}
        for d in self.devices:
            for name, _, dur in self.events(d, "XLA Ops"):
                tot[name] = tot.get(name, 0.0) + dur * 1e-9
        n = max(len(self.devices), 1)
        return sorted(([k_, v / n] for k_, v in tot.items()),
                      key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle time of the first device, summed by the innermost host span
        open at each gap's midpoint, the ``k`` largest."""
        if not self.devices:
            return []
        iv = self._intervals(min(self.devices))
        if len(iv) == 0:
            return []
        iv = iv[np.argsort(iv[:, 0])]
        ends = np.maximum.accumulate(iv[:, 1])
        gaps = [(self.t0, iv[0, 0])]
        gaps += [(ends[i], iv[i + 1, 0]) for i in range(len(iv) - 1)
                 if iv[i + 1, 0] > ends[i]]
        gaps.append((ends[-1], self.t1))
        spans = sorted((s for s in self.spans if s[0] != STRETCH),
                       key=lambda s: s[2])          # innermost first
        tot: dict = {}
        for a, b in gaps:
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            name = next((s[0] for s in spans if s[1] <= mid < s[1] + s[2]),
                        "no span")
            tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
        return sorted(([k_, v] for k_, v in tot.items()),
                      key=lambda kv: -kv[1])[:k]

    def summary(self) -> dict:
        """Plane/line/event-name overview, for reading a trace by hand."""
        out = {}
        for d, lines in self.devices.items():
            for ln, ev in lines.items():
                names: dict = {}
                for e in ev:
                    names[e[0]] = names.get(e[0], 0.0) + e[2]
                top = sorted(names.items(), key=lambda kv: -kv[1])[:25]
                out[f"{d}/{ln}"] = {"events": len(ev), "top": top}
        out["spans"] = len(self.spans)
        out["window_s"] = self.window_s
        return out
