"""Attribution of a traced window to the program's own names: device time
per layer scope, and host time per ``repro.*`` span.

Layer scopes. The program opens a ``jax.named_scope`` at each layer
boundary; the names are ``SCOPES`` here, which must equal the program's
``repro.obs.scopes.SCOPES``. A scope lands in the HLO ``op_name`` metadata
of every op the layer emits, forward and backward. The trace names each
device op by its HLO instruction (``xplane.short``), so each op's own time
in the window (``xplane.Chip.ops_ns``) goes to the innermost scope on its
instruction's ``op_name`` path, transform wrappers stripped (``scope_of``:
the rule is the benchmark's, not the program's). Ops in no scope, and ops
found in no module given, are ``UNSCOPED``. The modules are the optimized
HLO text of the executable that ran (``compiled.as_text()``, or
``hlo_modules()`` of the process's live executables while the step is still
referenced); of several, the one whose instructions cover most of the
window's own time is used. The program must have been compiled with its
scopes: JAX's persistent cache key leaves metadata out, so a cached
executable of the same program from before the scopes existed has none.

Host spans. With ``repro.obs.trace.annotate(True)`` the program's spans
(``repro.data.batch``, ``repro.train.*``) are profiler annotations on the
host plane, on the device trace's clock; ``span_totals`` sums them over the
window.

Nothing in the harness calls this yet: the harness keeps neither the step
nor the trace's host spans past the window. ``docs/scope_metrics.patch``
is the wiring that measured the readings in PERF.md.
"""
from __future__ import annotations

import re
import sys
from dataclasses import dataclass

import xplane

UNSCOPED = "unscoped"
SCOPES = ("embed", "attention", "mlp", "mixer", "ssd", "lm_head", "loss", "optimizer")
# a transform's wrapper around a name-stack entry: "jvp(mixer)",
# "transpose(jvp(ssd))"
WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[^\s=]+ = .*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str | None:
    """The innermost scope of ``SCOPES`` on an ``op_name`` path, or None.

    Transform wrappers are stripped from each entry before it is matched
    (``jit(step)/transpose(jvp(mixer))/ssd/dot_general`` -> ``ssd``); other
    entries, such as remat's ``checkpoint`` and ``rematted_computation``,
    match nothing.
    """
    found = None
    for part in op_name.split("/"):
        while (m := WRAPPED.match(part)) is not None:
            part = m.group(1)
        if part in SCOPES:
            found = part
    return found


@dataclass
class Scoped:
    ns: dict  # scope (or UNSCOPED) -> own ns in the window, mean over chips
    busy_ns: float  # union of the chips' ops, mean over chips
    unscoped_ops: list  # [(short name, own ns)] of unscoped ops, longest first

    def seconds(self, *names) -> float:
        return sum(self.ns.get(n, 0.0) for n in names) / 1e9

    def share(self, name: str) -> float:
        return self.ns.get(name, 0.0) / self.busy_ns


def instructions(hlo_text: str) -> dict:
    """Instruction name -> (short name, scope or None) of an HLO module's
    text, every computation's."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m is None:
            continue
        s = xplane.short(m.group(1))
        op = OP_NAME.search(line)
        out[s.split(" ")[0]] = (s, scope_of(op.group(1)) if op else None)
    return out


def match(ops_ns: dict, table: dict):
    """(short name -> scope or None, own ns covered) of the window's ops
    against one module's instructions. An op whose trace name has a type
    must have the instruction's type too."""
    found, covered = {}, 0.0
    for s, ns in ops_ns.items():
        hit = table.get(s.split(" ")[0])
        if hit is not None and (hit[0] == s or " " not in s):
            found[s] = hit[1]
            covered += ns
    return found, covered


def attribute(summary, tables) -> Scoped | None:
    """Own time per scope of a ``xplane.Summary``, with op names from the
    best-covering of ``tables`` (each the ``instructions`` of a module).
    None where no op of the window is in a scope: a program without them."""
    ops = {}
    for chip in summary.chips.values():
        for s, ns in chip.ops_ns.items():
            ops[s] = ops.get(s, 0.0) + ns
    # the module covering most own time; of equals (one program compiled
    # twice, once without its scopes), the one with most time in scopes
    best, key = {}, (0.0, 0.0)
    for table in tables:
        found, cov = match(ops, table)
        scoped = sum(ops[s] for s, scope in found.items() if scope)
        if (cov, scoped) > key:
            best, key = found, (cov, scoped)
    n = len(summary.chips)
    per, loose = {}, []
    for s, ns in ops.items():
        scope = best.get(s) or UNSCOPED
        per[scope] = per.get(scope, 0.0) + ns / n
        if scope == UNSCOPED:
            loose.append((s, ns / n))
    if not any(k in SCOPES for k in per):
        return None
    busy = sum(c.busy_ns for c in summary.chips.values()) / n
    return Scoped(per, busy, sorted(loose, key=lambda o: -o[1]))


def span_totals(evs, prefix: str = "repro.", window: str = "chipbench.window") -> dict:
    """Seconds of each host span named ``prefix...`` inside the host span
    ``window``, on the trace's clock (``xplane.events`` of a trace)."""
    evs = [(n, s, e) for p, _, n, s, e in evs if not xplane.DEVICE_PLANE.match(p)]
    lo, hi = next((s, e) for n, s, e in evs if n == window)
    out = {}
    for n, s, e in evs:
        if n.startswith(prefix) and e > lo and s < hi:
            out[n] = out.get(n, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    return out


def report(sc: Scoped | None, steps: int, file=sys.stderr) -> None:
    """The per-scope table of a traced window, and each unscoped op over 1%
    of the busy time."""
    if sc is None:
        print("layer scopes: none in the traced window", file=file)
        return
    print(f"layer scopes, own device time per step over {steps} steps "
          f"(busy {sc.busy_ns / steps / 1e6:.3f} ms a step):", file=file)
    for name, ns in sorted(sc.ns.items(), key=lambda kv: -kv[1]):
        ms, pct = ns / steps / 1e6, 100 * ns / sc.busy_ns
        print(f"  {name:<10} {ms:10.3f} ms  {pct:6.2f}%", file=file)
    total = sum(sc.ns.values())
    print(f"  sum of scopes {total / steps / 1e6:.3f} ms a step, "
          f"{100 * total / sc.busy_ns:.3f}% of busy", file=file)
    for s, ns in sc.unscoped_ops:
        if ns < 0.01 * sc.busy_ns:
            break
        print(f"  unscoped op {s}: {ns / steps / 1e6:.3f} ms a step, "
              f"{100 * ns / sc.busy_ns:.2f}% of busy", file=file)
