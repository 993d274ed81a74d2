"""Bounded compiled-program cache, shared by sessions and fleet cohorts.

Every compiled EL program's closure pins a device-resident copy of the
padded per-edge datasets, so an unbounded cache leaks device memory
under ever-changing keys (e.g. fresh ``metric_fn`` lambdas).  This is
the bounded FIFO ``ELSession`` has kept inline since the donation PR,
extracted so a :class:`repro.el.fleet.FleetServer` can share one cache
(and its hit/miss counters — the fleet's compiles-per-cohort assertion)
with the sessions that verify its tenants, and so ``close()`` /
``clear()`` can release the pinned buffers of long-lived servers.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, Optional


class ProgramCache:
    """Insertion-ordered dict of compiled programs with FIFO eviction.

    Mapping-shaped on purpose: ``len`` / ``in`` / iteration behave like
    the plain dict it replaces, so session internals (and the tests that
    poke them) keep working.  ``hits`` / ``misses`` / ``evictions``
    count ``get()``/``put()`` outcomes — a fleet cohort compiles exactly
    once iff every later lookup of its key is a hit — and are surfaced
    as a snapshot by :meth:`stats` (``ELReport.telemetry["cache"]``,
    the fleet CLI summary line).  An eviction also emits a
    ``cache.evict`` event on the process tracer (``repro.obs.trace``),
    since it marks a later recompile; a session's lookup outcome rides
    on its ``session.prepare`` span as ``cache="hit"`` / ``"miss"``.
    """

    def __init__(self, max_entries: int = 8):
        self.max_entries = int(max_entries)
        self._entries: Dict[tuple, Any] = {}
        # per-entry ProgramProfile side-store (repro.obs.prof): kept out
        # of _entries so cached values stay bare callables — session
        # internals (and the tests that poke them) treat entries as the
        # programs themselves.  Evicted with the entry.
        self._profiles: Dict[tuple, Any] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: tuple, default: Optional[Any] = None) -> Any:
        entry = self._entries.get(key, default)
        if entry is default:
            self.misses += 1
        else:
            self.hits += 1
        return entry

    def put(self, key: tuple, program: Any) -> Any:
        """Insert, evicting oldest entries past ``max_entries`` (any
        alias the caller keeps — e.g. the session's last-used fast-path
        handle — keeps an evicted program alive until replaced)."""
        from repro.obs import trace
        self._entries[key] = program
        while len(self._entries) > self.max_entries:
            evicted = next(iter(self._entries))
            self._entries.pop(evicted)
            self._profiles.pop(evicted, None)
            self.evictions += 1
            trace.event("cache.evict", evictions=self.evictions)
        return program

    def set_profile(self, key: tuple, profile: Any) -> Any:
        """Attach a :class:`repro.obs.prof.ProgramProfile` to a cached
        program (no-op for unknown keys — the entry may have been
        evicted between compile and profile)."""
        if key in self._entries:
            self._profiles[key] = profile
        return profile

    def profile(self, key: tuple) -> Optional[Any]:
        """The profile attached to a cached program (None when never
        profiled, or evicted)."""
        return self._profiles.get(key)

    def profiles(self) -> Dict[tuple, Any]:
        """Snapshot of every attached profile (key → ProgramProfile)."""
        return dict(self._profiles)

    def stats(self) -> Dict[str, int]:
        """Counter snapshot: entries/max_entries/hits/misses/evictions
        (+ how many entries carry a profile)."""
        return {
            "entries": len(self._entries),
            "max_entries": self.max_entries,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "profiled": len(self._profiles),
        }

    def clear(self) -> int:
        """Drop every cached program, returning how many were dropped.
        The programs' closures (and with them the device-resident
        datasets they pin) become collectible once callers also drop
        their aliases."""
        n = len(self._entries)
        self._entries.clear()
        self._profiles.clear()
        return n

    # -- dict-compatible surface ---------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: tuple) -> bool:
        return key in self._entries

    def __iter__(self) -> Iterator[tuple]:
        return iter(self._entries)

    def __getitem__(self, key: tuple) -> Any:
        return self._entries[key]

    def __setitem__(self, key: tuple, program: Any) -> None:
        self.put(key, program)

    def keys(self):
        return self._entries.keys()

    def values(self):
        return self._entries.values()

    def items(self):
        return self._entries.items()
