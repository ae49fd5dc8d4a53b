"""CommLedger: runtime collective-byte accounting against the paper bounds.

PR 4/5 could only audit communication inside tests — compile a program,
parse its HLO with ``roofline/hlo.collective_bytes_of``, assert the bytes
equal the closed forms.  The ledger makes that audit a *runtime* property
of every instrumented call-site: each site accumulates call counts and
(lazily, parsed once per compiled executable) the measured per-device
collective bytes of the executable it dispatches, next to the planner's
predicted words and the Theorem-2/3 floor.

Two site flavors:

  * :meth:`CommLedger.observe` — HLO-backed.  The call-site passes its
    jitted ``fn`` and the concrete call args; the ledger abstractifies the
    args into ``ShapeDtypeStruct``s (sharding preserved — shard_map byte
    counts depend on it) BEFORE the dispatch touches donated buffers, and
    stores a lazy thunk.  ``fn.lower(...).compile().as_text()`` runs only
    at first byte query (report time), hits XLA's compilation cache (the
    hot path already compiled this executable), and the parse is cached
    per (executable, signature) fingerprint — the hot-path cost after the
    first call at a signature is a tuple build + dict hit + counter bump.
  * :meth:`CommLedger.record` — analytic-only (no fn handle available,
    e.g. ``Plan.execute`` dispatching into opaque entry points): predicted
    words, floor and wall time accumulate; measured bytes stay None.

Per-site audit figures (mirroring ``plan.Plan.bound_ratio``):

  * ``bound_fraction`` — measured words/call over the Theorem-2/3 floor
    (1.0 when both are zero: a regime-1 schedule meeting a zero floor
    with zero traffic is *at* the bound, not off the scale);
  * ``drift``        — (measured - predicted) / predicted words: how far
    reality diverged from ``plan/model.py``.  Sites opened with an
    autotune ``cache_key`` feed ``obs.report.revalidate_autotune``.
"""
from __future__ import annotations

import math
import threading
from typing import Any, Dict, Optional, Tuple


def _sig_of(args: Tuple) -> Tuple:
    """Cheap structural signature of a call's args (shape/dtype per array;
    scalars and None verbatim) — the per-(site, executable) ledger key.
    Dtype objects are kept verbatim (hashable); stringifying them is ~2us
    of numpy machinery per array, which the hot path cannot afford."""
    out = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            out.append((shape if type(shape) is tuple else tuple(shape),
                        getattr(a, "dtype", None)))
        else:
            out.append(a)
    return tuple(out)


def _abstractify(args: Tuple) -> Tuple:
    """ShapeDtypeStructs (sharding preserved) for lazy re-lowering without
    holding or donating the concrete buffers."""
    import jax
    out = []
    for a in args:
        if getattr(a, "shape", None) is not None and hasattr(a, "dtype"):
            sharding = getattr(a, "sharding", None)
            # Only mesh shardings constrain the lowering; a scalar operand
            # committed to one device (e.g. a jnp.int32 row offset) would
            # otherwise pin lower() to that device and conflict with the
            # mesh-sharded operands — jit replicates it at dispatch anyway.
            if not isinstance(sharding,
                              getattr(jax.sharding, "NamedSharding", ())):
                sharding = None
            try:
                out.append(jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=sharding))
            except TypeError:       # older jax: no sharding kwarg
                out.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
        else:
            out.append(a)
    return tuple(out)


class LedgerSite:
    """One (call-site name, executable signature) accumulator."""

    def __init__(self, name: str, sig: Tuple, *,
                 predicted_words: float = 0.0,
                 lower_bound_words: float = 0.0,
                 itemsize: int = 4,
                 cache_key: Optional[str] = None,
                 hlo_thunk=None):
        self.name = name
        self.sig = sig
        self.predicted_words = float(predicted_words)
        self.lower_bound_words = float(lower_bound_words)
        self.itemsize = int(itemsize)
        self.cache_key = cache_key
        self.calls = 0
        self.wall_s = 0.0
        self._hlo_thunk = hlo_thunk
        self._cb = None             # cached CollectiveBytes (or False: n/a)

    # -- measured bytes (lazy, parsed once) ---------------------------------

    def compiled_text(self) -> Optional[str]:
        """The site's compiled executable as HLO text (None for
        analytic-only sites): re-lowered at the dispatched signature, so
        the compile is the one the dispatch cached."""
        return None if self._hlo_thunk is None else self._hlo_thunk()

    def collectives(self):
        """The executable's parsed :class:`CollectiveBytes` (None for
        analytic-only sites); lowers + parses on first call, then cached."""
        if self._cb is None:
            if self._hlo_thunk is None:
                self._cb = False
            else:
                from repro.roofline.hlo import collective_bytes_of
                self._cb = collective_bytes_of(self.compiled_text())
        return None if self._cb is False else self._cb

    @property
    def measured_bytes_per_call(self) -> Optional[float]:
        cb = self.collectives()
        return None if cb is None else cb.total

    @property
    def measured_bytes(self) -> Optional[float]:
        per = self.measured_bytes_per_call
        return None if per is None else per * self.calls

    @property
    def measured_words_per_call(self) -> Optional[float]:
        per = self.measured_bytes_per_call
        return None if per is None else per / self.itemsize

    # -- audit figures ------------------------------------------------------

    @property
    def bound_fraction(self) -> Optional[float]:
        """Measured words/call over the Theorem-2/3 floor; the zero/zero
        convention matches ``plan.Plan.bound_ratio``."""
        m = self.measured_words_per_call
        if m is None:
            return None
        if self.lower_bound_words == 0.0:
            return 1.0 if m == 0.0 else math.inf
        return m / self.lower_bound_words

    @property
    def drift(self) -> Optional[float]:
        """(measured - predicted) / predicted words per call."""
        m = self.measured_words_per_call
        if m is None:
            return None
        if self.predicted_words == 0.0:
            return 0.0 if m == 0.0 else math.inf
        return (m - self.predicted_words) / self.predicted_words

    def __repr__(self):
        m = self.measured_bytes_per_call
        return (f"LedgerSite({self.name!r}, calls={self.calls}, "
                f"bytes/call={'n/a' if m is None else f'{m:.6g}'}, "
                f"predicted_words={self.predicted_words:.6g}, "
                f"floor={self.lower_bound_words:.6g})")


class CommLedger:
    """Accumulates :class:`LedgerSite`s across every instrumented path."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sites: Dict[Tuple, LedgerSite] = {}

    # -- hot-path API -------------------------------------------------------

    def observe(self, name: str, fn, args: Tuple, *,
                predicted_words: float = 0.0,
                lower_bound_words: float = 0.0,
                itemsize: int = 4,
                cache_key: Optional[str] = None,
                wall_s: Optional[float] = None,
                count: int = 1) -> LedgerSite:
        """Account one dispatch of jitted ``fn`` called with ``args``.

        Call BEFORE the dispatch when any arg is donated — the ledger
        abstractifies immediately and never touches the buffers again.
        """
        sig = _sig_of(args)
        key = (name, sig)
        site = self._sites.get(key)
        if site is None:
            abs_args = _abstractify(args)
            site = LedgerSite(
                name, sig, predicted_words=predicted_words,
                lower_bound_words=lower_bound_words, itemsize=itemsize,
                cache_key=cache_key,
                hlo_thunk=lambda: fn.lower(*abs_args).compile().as_text())
            with self._lock:
                site = self._sites.setdefault(key, site)
        site.calls += count
        if wall_s is not None:
            site.wall_s += wall_s
        return site

    def record(self, name: str, *,
               predicted_words: float = 0.0,
               lower_bound_words: float = 0.0,
               itemsize: int = 4,
               cache_key: Optional[str] = None,
               wall_s: Optional[float] = None,
               detail: Any = None,
               count: int = 1) -> LedgerSite:
        """Analytic-only site (no executable handle): predictions, floor
        and wall time accumulate; measured bytes stay unavailable."""
        key = (name, ("analytic", detail))
        site = self._sites.get(key)
        if site is None:
            site = LedgerSite(name, key[1],
                              predicted_words=predicted_words,
                              lower_bound_words=lower_bound_words,
                              itemsize=itemsize, cache_key=cache_key)
            with self._lock:
                site = self._sites.setdefault(key, site)
        site.calls += count
        if wall_s is not None:
            site.wall_s += wall_s
        return site

    # -- queries ------------------------------------------------------------

    def sites(self):
        with self._lock:
            return list(self._sites.values())

    def site(self, name: str) -> Optional[LedgerSite]:
        """The single site registered under ``name`` (first match)."""
        for s in self.sites():
            if s.name == name:
                return s
        return None

    def total_measured_bytes(self, name: Optional[str] = None) -> float:
        """Measured bytes summed over calls (and, with ``name``, restricted
        to that site name) — analytic-only sites contribute nothing."""
        tot = 0.0
        for s in self.sites():
            if name is not None and s.name != name:
                continue
            b = s.measured_bytes
            if b is not None:
                tot += b
        return tot

    def clear(self) -> None:
        with self._lock:
            self._sites.clear()

    def __len__(self):
        return len(self._sites)


# -- module-level install point ----------------------------------------------

_ledger: Optional[CommLedger] = None


def get_ledger() -> Optional[CommLedger]:
    return _ledger


def install_ledger(ledger: Optional[CommLedger] = None) -> CommLedger:
    global _ledger
    _ledger = ledger if ledger is not None else CommLedger()
    return _ledger


def uninstall_ledger() -> Optional[CommLedger]:
    global _ledger
    prev, _ledger = _ledger, None
    return prev
