"""Per-region secondary tag index: tag-value -> sid postings.

The capability analog of the reference's inverted index appliers
(src/index + the puffin blobs mito2 attaches to SSTs): instead of a
separate on-disk index format, the postings are derived from the
dictionary-coded label plane the series registry already maintains —
per tag column, a CSR (offsets, order) pair where order is the stable
argsort of that column's codes, so the sids for one tag value are a
contiguous ascending slice.

Matcher evaluation splits into two domains, chosen by the matcher's op:

- `eq`/`in` matchers resolve each literal to its dictionary code through
  the dictionary's own hash (Dictionary.lookup, O(1) per literal) and
  read the posting slices — no pass over the dictionary's values and no
  per-series work at all. Their accepting set is a small sorted array
  of codes; a literal the dictionary lacks contributes none.
- `re`/`nre`/`ne`/`nin` matchers evaluate once per DISTINCT value
  (series.ok_codes_for — the same code match_mask broadcasts through):
  their verdict is a property of every value, so they keep a bool
  ok-table over the dictionary. String/regex cost scales with value
  cardinality, not series cardinality.

The most selective matcher (estimated from the posting lengths of its
accepting codes) seeds the candidate set; the rest filter candidates by
their codes — an integer compare against the code set, or an index
into the ok-table — O(|candidates|) int work per matcher.
`gtpu_index_lookups_total{path}` says which a lookup took: `codes` when
no ok-table was built, `postings` when at least one was.

Maintenance is incremental and version-validated like the scan cache:
sids are dense and append-only, so postings built at registry version v
cover a sid PREFIX; series registered since are evaluated directly
(O(delta)) until the delta crosses `rebuild_threshold` and the CSR is
rebuilt. ALTER ADD TAG (column-count change) always rebuilds. Matched
sid sets are memoized per canonical matcher key, keyed on the registry
version (an eq lookup repeated across a dashboard poll costs one dict
hit).
"""

from __future__ import annotations

import weakref
from collections import OrderedDict

import numpy as np

from greptimedb_tpu import concurrency
from greptimedb_tpu.storage.series import missing_tag_ok, ok_codes_for

_CFG = {
    "enable": True,
    # device-resident label plane (index/device_plane.py)
    "device_plane": True,
    # per-index memoized (matcher-set -> sids) entries
    "result_cache_entries": 256,
    # series registered since the last CSR build before a rebuild;
    # below it the delta tail is evaluated directly per lookup
    "rebuild_threshold": 4096,
}


def configure(section: dict | None) -> None:
    """Apply the [index] config section (config.DEFAULTS['index'])."""
    for k, v in (section or {}).items():
        if k in _CFG:
            _CFG[k] = v
    if not _CFG["device_plane"] or not _CFG["enable"]:
        from greptimedb_tpu.index import device_plane

        device_plane.invalidate()


def enabled() -> bool:
    return bool(_CFG["enable"])


def device_plane_enabled() -> bool:
    return bool(_CFG["enable"]) and bool(_CFG["device_plane"])


def matcher_key(matchers) -> tuple:
    """Canonical hashable key for a matcher set: compiled regexes fold
    to their pattern string, list values to tuples. Order-sensitive
    (matcher sets arrive in plan order, which is stable per statement
    fingerprint)."""
    out = []
    for name, op, value in matchers:
        if op in ("re", "nre"):
            v = getattr(value, "pattern", value)
        elif isinstance(value, (list, tuple, set, frozenset)):
            v = tuple(sorted(str(x) for x in value))
        else:
            v = value
        out.append((name, op, v))
    return tuple(out)


def _expand_csr(offsets: np.ndarray, order: np.ndarray,
                codes: np.ndarray) -> np.ndarray:
    """Gather the concatenated posting slices for `codes` (vectorized
    multi-slice CSR expand — no per-code Python loop)."""
    if len(codes) == 0:
        return np.zeros(0, dtype=np.int32)
    if len(codes) == 1:
        c = int(codes[0])
        return order[offsets[c]:offsets[c + 1]].copy()
    starts = offsets[codes]
    lens = offsets[codes + 1] - starts
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int32)
    pos = np.repeat(starts - (np.cumsum(lens) - lens), lens)
    return order[pos + np.arange(total, dtype=np.int64)]


def _lookup_codes(d, op: str, value) -> np.ndarray:
    """Accepting codes of an eq/in matcher: the codes dictionary `d`
    holds for the matcher's literals, distinct and ascending (int64).
    One hash lookup per literal; a literal `d` lacks has no code."""
    if op == "eq":
        c = d.lookup(value)
        found = [] if c is None else [c]
    else:
        found = sorted({c for c in map(d.lookup, value) if c is not None})
    return np.asarray(found, dtype=np.int64)


def _posted_codes(cs, ok, nvals: int) -> np.ndarray:
    """The accepting codes of one matcher form (code set `cs` or
    ok-table `ok`) among the `nvals` codes the CSR has slices for."""
    if ok is None:
        return cs[cs < nvals]
    return np.flatnonzero(ok[:nvals])


def _accepts(cs, ok, c: np.ndarray) -> np.ndarray:
    """Verdict of one matcher form on a vector of codes: an integer
    compare against the code set, or an index into the ok-table (codes
    past the table were interned after it was made: rejected)."""
    if ok is None:
        return c == cs[0] if len(cs) == 1 else np.isin(c, cs)
    safe = np.minimum(c, len(ok) - 1)
    return ok[safe] & (c < len(ok))


class TagIndex:
    """Secondary index over one SeriesRegistry (see module docstring)."""

    def __init__(self, registry):
        self._reg = registry
        self._lock = concurrency.Lock()
        self._built_version = -1
        self._built_rows = 0
        self._built_tags = 0
        # per tag column: (offsets int64 (nvals+1,), order int32) over
        # the first _built_rows sids
        self._postings: list[tuple[np.ndarray, np.ndarray]] = []
        self._results: OrderedDict[tuple, tuple[int, np.ndarray]] = (
            OrderedDict()
        )
        self._hits = 0
        self._misses = 0
        self._builds = 0
        _track(self)

    # -- maintenance ---------------------------------------------------
    def _ensure_built(self, codes: np.ndarray, version: int) -> int:
        """Bring postings up to date for the (n, k) snapshot `codes`;
        returns the prefix length the CSR covers. Caller holds no lock —
        builds race benignly (last writer wins, both are correct)."""
        n, k = codes.shape
        if version == self._built_version and k == self._built_tags:
            return self._built_rows
        if (k == self._built_tags and self._built_rows <= n
                and n - self._built_rows <= int(_CFG["rebuild_threshold"])):
            # delta tail small: validate the version without rebuilding
            # (lookups evaluate sids >= _built_rows directly)
            self._built_version = version
            return self._built_rows
        dicts = self._reg.dicts
        postings = []
        for i in range(k):
            col = codes[:, i]
            nvals = max(len(dicts[i]) if i < len(dicts) else 0,
                        int(col.max()) + 1 if n else 0)
            counts = np.bincount(col, minlength=nvals)
            offsets = np.zeros(len(counts) + 1, dtype=np.int64)
            np.cumsum(counts, out=offsets[1:])
            # stable argsort keeps original (ascending-sid) order within
            # each code, so every posting slice is already sorted
            order = np.argsort(col, kind="stable").astype(np.int32)
            postings.append((offsets, order))
        with self._lock:
            self._postings = postings
            self._built_rows = n
            self._built_tags = k
            self._built_version = version
            self._builds += 1
        return n

    # -- lookup --------------------------------------------------------
    def match_sids(self, matchers) -> np.ndarray:
        """Sids satisfying all matchers, ascending int32 — bit-identical
        to SeriesRegistry.match_sids (the same verdict per distinct
        value, reached through the dictionary's hash for eq/in and the
        shared ok-code tables otherwise, then broadcast through postings
        instead of the full plane)."""
        from greptimedb_tpu.query import stats

        reg = self._reg
        version = reg.version
        key = matcher_key(matchers)
        with self._lock:
            hit = self._results.get(key)
            if hit is not None and hit[0] == version:
                self._results.move_to_end(key)
                self._hits += 1
                _count_lookup("cache")
                stats.add("index_lookups", 1)
                return hit[1]
            self._misses += 1
        sids, path = self._eval(matchers, version)
        with self._lock:
            self._results[key] = (version, sids)
            self._results.move_to_end(key)
            cap = int(_CFG["result_cache_entries"])
            while len(self._results) > max(cap, 1):
                self._results.popitem(last=False)
        _count_lookup(path)
        stats.add("index_lookups", 1)
        return sids

    def match_mask(self, matchers) -> np.ndarray:
        """(num_series,) bool mask via the index (postings expanded back
        into a dense mask — what the device plane ok-tables mirror)."""
        n = self._reg.num_series
        mask = np.zeros(n, dtype=bool)
        sids = self.match_sids(matchers)
        mask[sids[sids < n]] = True
        return mask

    def _eval(self, matchers, version: int) -> tuple[np.ndarray, str]:
        """Evaluate a matcher set; returns (sids, path) where path is
        "codes" when every matcher was resolved through its dictionary's
        hash and "postings" when at least one ok-table was built."""
        reg = self._reg
        codes = reg.codes_matrix()
        n, k = codes.shape
        empty = np.zeros(0, dtype=np.int32)
        path = "codes"
        if n == 0:
            return empty, path
        tag_names = reg.tag_names
        dicts = reg.dicts
        # dictionary-domain pass, one accepting form per matcher:
        # (column, sorted codes, None) for eq/in — hash lookups, the
        # dictionary's values are never walked — or (column, None,
        # ok-table) for the ops whose verdict needs every value
        forms: list[tuple[int, np.ndarray | None, np.ndarray | None]] = []
        for name, op, value in matchers:
            if name not in tag_names:
                if not missing_tag_ok(op, value):
                    return empty, path
                continue  # constant-true: no constraint
            i = tag_names.index(name)
            if op in ("eq", "in"):
                cs = _lookup_codes(dicts[i], op, value)
                if len(cs) == 0:
                    return empty, path
                forms.append((i, cs, None))
                continue
            path = "postings"
            vals = np.asarray(list(dicts[i].values), dtype=object)
            ok = ok_codes_for(vals, op, value)
            if not ok.any():
                return empty, path
            forms.append((i, None, ok))
        if not forms:
            return np.arange(n, dtype=np.int32), path
        built = self._ensure_built(codes, version)
        postings = self._postings
        # seed candidates from the most selective matcher (estimated
        # from the posting lengths of its accepting codes over the built
        # prefix; a code interned since the build has no slice yet and
        # counts 0 — its series live in the delta tail only)
        seed = -1
        if built and postings:
            best = None
            for j, (i, cs, ok) in enumerate(forms):
                offsets, _ = postings[i]
                acc = _posted_codes(cs, ok, len(offsets) - 1)
                est = int((offsets[acc + 1] - offsets[acc]).sum())
                if best is None or est < best:
                    best, seed, seed_codes = est, j, acc
            offsets, order = postings[forms[seed][0]]
            cand = _expand_csr(offsets, order, seed_codes)
            if len(seed_codes) > 1:
                # each posting slice is ascending; a multi-code union
                # needs one merge sort to restore global sid order
                cand = np.sort(cand)
        else:
            cand = np.arange(built, dtype=np.int32)
        # remaining matchers filter candidates by the candidates' codes
        for j, (i, cs, ok) in enumerate(forms):
            if j == seed:
                continue
            if len(cand) == 0:
                break
            cand = cand[_accepts(cs, ok, codes[cand, i])]
        # delta tail (sids registered since the CSR build): direct
        # evaluation over O(delta) rows
        if built < n:
            keep = np.ones(n - built, dtype=bool)
            for i, cs, ok in forms:
                keep &= _accepts(cs, ok, codes[built:, i])
            tail = (np.flatnonzero(keep) + built).astype(np.int32)
            if len(tail):
                cand = np.concatenate([cand.astype(np.int32), tail])
        return np.ascontiguousarray(cand, dtype=np.int32), path

    # -- observability -------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            return {
                "built_rows": self._built_rows,
                "built_version": self._built_version,
                "builds": self._builds,
                "hits": self._hits,
                "misses": self._misses,
                "cached_results": len(self._results),
                "bytes": self.nbytes(),
            }

    def nbytes(self) -> int:
        total = 0
        for offsets, order in self._postings:
            total += int(offsets.nbytes) + int(order.nbytes)
        for _, sids in self._results.values():
            total += int(sids.nbytes)
        return total


# ---------------------------------------------------------------------
# registry -> index association + host memory-pool accounting
# ---------------------------------------------------------------------
_INDEXES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_INDEXES_LOCK = concurrency.Lock()
# separate from _INDEXES_LOCK: TagIndex.__init__ runs under it (via
# index_for) and _track must not re-acquire the same non-reentrant lock
_POOL_LOCK = concurrency.Lock()
_POOL_REGISTERED = False
_LIVE: "weakref.WeakSet[TagIndex]" = weakref.WeakSet()


class _IndexPool:
    """Accountant surface over every live TagIndex (host tier)."""

    def stats(self) -> dict:
        total = entries = hits = misses = 0
        for ix in list(_LIVE):
            s = ix.stats()
            total += s["bytes"]
            entries += s["cached_results"]
            hits += s["hits"]
            misses += s["misses"]
        return {
            "bytes": total, "entries": entries, "budget_bytes": 0,
            "hits": hits, "misses": misses, "evictions": 0,
        }


_POOL = _IndexPool()


def _track(ix: TagIndex) -> None:
    global _POOL_REGISTERED
    _LIVE.add(ix)
    with _POOL_LOCK:
        if _POOL_REGISTERED:
            return
        _POOL_REGISTERED = True
    from greptimedb_tpu.telemetry import memory as _memory

    _memory.register_pool(
        "tag_index", "host", _POOL, stats=_IndexPool.stats,
    )


def _count_lookup(path: str) -> None:
    from greptimedb_tpu.telemetry.metrics import global_registry

    global_registry.counter(
        "gtpu_index_lookups_total",
        "Secondary tag-index matcher lookups by path "
        "(cache | codes | postings | host)",
        labels=("path",),
    ).labels(path).inc()


def count_pruned(*, row_groups: int = 0, bytes_: int = 0,
                 scope: str = "row_group") -> None:
    """Record scan data skipped by sid-range/sid-index pruning, in the
    per-query ExecStats (EXPLAIN ANALYZE) and the process counters.
    scope: "row_group" (footer sid-index) | "sst" (manifest sid range)."""
    from greptimedb_tpu.query import stats
    from greptimedb_tpu.telemetry.metrics import global_registry

    if row_groups:
        stats.add("index_pruned_row_groups", row_groups)
        global_registry.counter(
            "gtpu_index_pruned_row_groups_total",
            "Row groups skipped by the secondary-index sid pruning",
        ).inc(row_groups)
    if bytes_:
        stats.add("index_pruned_bytes", bytes_)
        global_registry.counter(
            "gtpu_index_pruned_bytes_total",
            "Bytes skipped by secondary-index sid pruning "
            "(sst = whole files via the manifest sid range, "
            "row_group = Parquet row groups via the footer sid index)",
            labels=("scope",),
        ).labels(scope).inc(bytes_)


def index_for(registry) -> TagIndex:
    """The TagIndex for a registry (one per registry, weakly held — a
    region swapping its registry on replay/restore drops the old index
    with it)."""
    with _INDEXES_LOCK:
        ix = _INDEXES.get(registry)
        if ix is None:
            ix = TagIndex(registry)
            _INDEXES[registry] = ix
        return ix


def match_sids(registry, matchers) -> np.ndarray:
    """Route a matcher lookup through the secondary index when enabled;
    the registry's full-plane compare is the fallback (and the oracle
    the index tests equate against)."""
    if not matchers:
        return np.arange(registry.num_series, dtype=np.int32)
    if not _CFG["enable"]:
        _count_lookup("host")
        return registry.match_sids(matchers)
    return index_for(registry).match_sids(matchers)


def match_mask(registry, matchers) -> np.ndarray:
    """Dense bool mask counterpart of match_sids (PromQL grid path)."""
    if not matchers:
        return np.ones(registry.num_series, dtype=bool)
    if not _CFG["enable"]:
        _count_lookup("host")
        return registry.match_mask(matchers)
    return index_for(registry).match_mask(matchers)
