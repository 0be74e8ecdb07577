"""Process-local metrics: one rate, one merge, a registry of live stats.

The repo's stats surfaces (``EngineStats``, ``StoreStats``, ``CascadeStats``,
``ServeStats``) each carry monotone counters plus derived ``*_rate``
properties. Before this module, every aggregation point re-implemented the
fold by hand (``session.nested`` summed numerics and recomputed one rate;
``executor._aggregate_stats`` did the same for store counters) — and each
copy had its own bugs (dropped non-numeric keys, summed rates). Two
primitives replace all of that:

* :func:`rate` — the single definition of a hit/prune/cache rate
  (``num / max(den, 1)``) used by every ``*_rate`` property in the repo;
* :func:`merge_stats` — fold N ``as_dict()`` outputs into one: counters sum,
  non-numeric keys pass through, and every known ``*_rate`` key is
  recomputed from the SUMMED counters (averaging per-shard rates would
  weight an idle shard the same as a busy one).

:class:`MetricsRegistry` is the process-local registry on top: weak
registration of live stats objects, so ``export()`` can snapshot everything
observable in the process without any surface pushing updates.

Stdlib only; safe to import from any layer.
"""

from __future__ import annotations

import threading
import weakref
from typing import Iterable, Mapping, Optional

__all__ = [
    "rate",
    "merge_stats",
    "MetricsRegistry",
    "REGISTRY",
]


def rate(num: float, den: float) -> float:
    """THE rate definition: ``num / max(den, 1)`` (0 when nothing happened,
    never a ZeroDivisionError). Every ``*_rate`` surface routes through
    here so a rate means the same thing on every layer."""
    return num / max(den, 1)


#: How each known ``*_rate`` key is recomputed after counters are summed.
#: Each value is an ordered tuple of ``(numerator, denominator)`` counter-key
#: candidates — the first pair whose numerator key exists in the merged dict
#: wins. ``hit_rate`` needs two candidates because the store
#: (``hits/gets``) and the engine (``cache_hits/requested``) both expose a
#: key of that name over different counters.
RATE_SPECS: dict[str, tuple[tuple[str, str], ...]] = {
    "hit_rate": (("hits", "gets"), ("cache_hits", "requested")),
    "cross_hit_rate": (("cross_hits", "gets"),),
    "cache_hit_rate": (("cache_hits", "queries"),),
    "prune_rate": (("pruned", "requested"),),
}


def merge_stats(
    stats: Iterable[Mapping],
    defaults: Optional[Mapping[str, float]] = None,
) -> dict:
    """Fold N stats dicts (``as_dict()`` outputs) into one.

    * int/float/bool values sum (bools count occurrences);
    * ``*_rate`` keys are never summed — every key named in
      :data:`RATE_SPECS` whose counters are present is recomputed from the
      summed counters;
    * non-numeric values pass through: a single distinct value stays
      scalar, disagreeing values become the sorted list of distinct
      reprs (nothing is silently dropped);
    * ``defaults`` seeds counter keys (e.g. ``{"gets": 0}``) so the merged
      schema is stable even when the input list is empty.
    """
    total: dict = dict(defaults or {})
    passthrough: dict[str, list] = {}
    for s in stats:
        for key, v in s.items():
            if key in RATE_SPECS or key.endswith("_rate"):
                continue  # recomputed below, never summed
            if isinstance(v, bool) or isinstance(v, (int, float)):
                total[key] = total.get(key, 0) + v
            else:
                passthrough.setdefault(key, [])
                if v not in passthrough[key]:
                    passthrough[key].append(v)
    for key, vals in passthrough.items():
        total[key] = vals[0] if len(vals) == 1 else sorted(map(repr, vals))
    for key, candidates in RATE_SPECS.items():
        for num, den in candidates:
            if num in total:
                total[key] = rate(total[num], total.get(den, 0))
                break
    return total


# ---- registry -------------------------------------------------------------


class MetricsRegistry:
    """Process-local registry of weakly-held stats objects.

    ``register(group, obj)`` holds a weakref to any object with
    ``as_dict()`` (the repo's stats dataclasses self-register on
    construction); transient engines/stores vanish from ``export()`` when
    they are garbage collected, so a long process running thousands of
    searches never leaks registry entries. ``export()`` snapshots each
    stats group folded through :func:`merge_stats`.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: dict[str, list] = {}  # group -> [weakref]

    def register(self, group: str, obj) -> None:
        """Weakly register a live stats object (anything with ``as_dict``)
        under ``group``; dead refs are pruned opportunistically."""
        with self._lock:
            refs = self._stats.setdefault(group, [])
            refs.append(weakref.ref(obj))
            if len(refs) > 256:
                self._stats[group] = [r for r in refs if r() is not None]

    # merge_stats re-exported as a method so callers holding only a registry
    # (or the class) can fold dicts without a second import
    merge = staticmethod(merge_stats)

    def export(self) -> dict:
        """One dict of everything observable in this process right now."""
        with self._lock:
            out: dict = {"stats": {}}
            for group, refs in self._stats.items():
                live = [r() for r in refs]
                live = [o for o in live if o is not None]
                self._stats[group] = [weakref.ref(o) for o in live]
                merged = merge_stats(o.as_dict() for o in live)
                merged["instances"] = len(live)
                out["stats"][group] = merged
        return out

    def reset(self) -> None:
        """Drop all registrations (tests/benchmarks)."""
        with self._lock:
            self._stats.clear()


#: The process-default registry the stats dataclasses register into.
REGISTRY = MetricsRegistry()
