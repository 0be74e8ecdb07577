"""What every loop of the benchmark shares: host spans on the benchmark's
clock, the count of compilations, the traced window, the device's peak
memory, and the comparisons that decide ``correct``."""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import jax

import xplane

HERE = Path(__file__).resolve().parent


def load_json(rel: str) -> dict:
    return json.loads((HERE / rel).read_text())


def load_module(rel: str):
    """A module of the benchmark found by its file name (names may hold dots)."""
    path = HERE / rel
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Job:
    """One run of one cell."""
    cell: str
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    t0: float  # process start on time.perf_counter()
    calibrate: bool = False  # also read the control and the faults


@dataclass
class Result:
    kind: str
    e2e: dict = field(default_factory=dict)
    counts: dict = field(default_factory=dict)
    steps: int = 0  # steps in the traced window
    trace: object = None  # trace.Summary of the traced window
    host: dict = field(default_factory=dict)  # host span totals, seconds
    checks: list = field(default_factory=list)  # (name, value, limit)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    window_compiles: int = 0
    calibration: dict = field(default_factory=dict)

    def check(self, name: str, value: float, limits: dict) -> None:
        self.checks.append((name, float(value), limits.get(name)))

    @property
    def correct(self) -> bool:
        return self.window_compiles == 0 and bool(self.checks) and all(
            lim is not None and v == v and v <= lim for _, v, lim in self.checks)


class Spans:
    """Host spans: each is a profiler TraceAnnotation and a total on the
    benchmark's perf_counter clock."""

    def __init__(self):
        self.totals = defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.totals[name] += time.perf_counter() - t


class Compiles:
    """Counts tracing and backend compilation while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.armed = False
        self.count = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="", **_):
        if self.armed and event in self.EVENTS:
            self.count += 1
            self.names.append(fun_name)


COMPILES = None


def compiles() -> Compiles:
    global COMPILES
    if COMPILES is None:
        COMPILES = Compiles()
    return COMPILES


class Profile:
    """The profiler of a traced run. A loop starts it before its last set-up
    step, so that the profiler's own start-up falls outside the window."""

    def __init__(self, traced: bool):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None

    def start(self) -> None:
        if self.dir:
            jax.profiler.start_trace(self.dir)

    def summary(self):
        jax.profiler.stop_trace()
        try:
            pb = sorted(Path(self.dir).rglob("*.xplane.pb"))[-1]
            return xplane.summarize(xplane.events(xplane.load(str(pb))))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def settle() -> None:
    """The last step of set-up: collect, then freeze what set-up made, so
    that no collection inside the window walks set-up's heap."""
    gc.collect()
    gc.freeze()


class Pauses:
    """The garbage collector's pauses: how many, their sum and the longest."""

    def __init__(self):
        self.n, self.total, self.longest, self.t = 0, 0.0, 0.0, None

    def __call__(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            d = time.perf_counter() - self.t
            self.n, self.total, self.longest = self.n + 1, self.total + d, max(self.longest, d)


@contextlib.contextmanager
def window(res: Result, profile: Profile, spans: Spans):
    """The measured window: compilations inside it are counted, and the
    collector's pauses; in a traced run the profiler stops after it and
    ``res.trace`` gets the summary."""
    c = compiles()
    pauses = Pauses()
    gc.callbacks.append(pauses)
    c.armed, before = True, c.count
    try:
        with spans("chipbench.window"):
            yield
    finally:
        c.armed = False
        gc.callbacks.remove(pauses)
        res.window_compiles = c.count - before
        print(f"collections in the window: {pauses.n}, {pauses.total!r} s, longest "
              f"{pauses.longest!r} s", file=sys.stderr)
        if profile.dir:
            res.trace = profile.summary()


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def free(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Each leaf's gap between two norms, over the larger of that leaf's
    reference norm and the median leaf's."""
    keys = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}
