"""Host-side global string dictionaries.

Strings never reach the TPU: every VARCHAR column is dictionary-encoded at
load/ingest time into int32 codes, with the code->string mapping kept on the
host. Joins and group-bys on strings become integer problems on device.

Reference precedent: OceanBase's per-micro-block dictionary encodings
(storage/blocksstable/encoding/ob_dict_decoder_simd.cpp and
cs_encoding/ob_dict_column_decoder_simd.cpp). The TPU redesign promotes the
dictionary from a block-local compression detail to the *global* physical
representation of the column, because device kernels cannot chase varlen
bytes.

Two dictionary flavors:

- Dictionary: insertion-ordered, codes are arbitrary. O(1) encode.
- SortedDictionary: codes are assigned in lexicographic order so that
  code comparison == string comparison; required when range predicates
  (<, >, BETWEEN, ORDER BY) apply to the column. Built by finalizing an
  unsorted dictionary, or kept by `SortedViews` as the append-order one
  grows.

Append-order dictionaries are shared by every session writing the table:
an append takes the dictionary's lock, and a reader works from one
`snapshot()` (the value list and its length, taken once), since the list
only ever grows at its end.
"""

from __future__ import annotations

import bisect
import itertools
import threading

import numpy as np


class Dictionary:
    """Insertion-ordered string <-> int32 code mapping.

    `lineage` is set on the sorted views `SortedViews` keeps of one
    append-order dictionary: the versions of one column share it, which is
    what lets a compiled program that reads none of their contents serve
    every version (`DictPin`)."""

    __slots__ = ("_values", "_index", "sorted", "lineage", "_lock")

    def __init__(self, values: list[str] | None = None, sorted_: bool = False):
        self._values: list[str] = [] if values is None else list(values)
        self._index: dict[str, int] | None = {
            v: i for i, v in enumerate(self._values)}
        self.sorted = sorted_
        self.lineage = None
        self._lock = threading.Lock()

    @classmethod
    def view(cls, values: "Chunked", lineage) -> "Dictionary":
        """A read-only sorted dictionary over `values` (taken, not
        copied): lookups bisect, so no string index is built."""
        d = cls.__new__(cls)
        d._values = values
        d._index = None
        d.sorted = True
        d.lineage = lineage
        d._lock = None
        return d

    def __len__(self) -> int:
        return len(self._values)

    def __reduce__(self):
        # a lock does not pickle; a view comes back a view, its lineage
        # (a process's own) left behind
        if self._lock is None:
            return (Dictionary.view, (self._values, None))
        return (Dictionary, (self._values, self.sorted))

    def snapshot(self) -> tuple[list[str], int]:
        """(values, n): entries [0, n) of the list are this version, and
        stay so while other sessions append."""
        vals = self._values
        return vals, len(vals)

    def domain(self, cap: int) -> int:
        """The code domain a program may size itself by, exact up to
        `cap` and `cap + 1` past it: a group-by that takes a direct path
        only for small domains depends on no more than this."""
        return min(len(self._values), cap + 1)

    def _lookup(self, s: str) -> int | None:
        if self._index is not None:
            return self._index.get(s)
        vals = self._values  # a sorted view
        i = vals.bisect_left(s)
        return i if i < len(vals) and vals[i] == s else None

    def encode_one(self, s: str, add: bool = True) -> int:
        code = self._lookup(s)
        if code is None:
            if not add:
                return -1
            if self._lock is None:
                raise TypeError("a sorted dictionary view is read-only")
            with self._lock:
                code = self._index.get(s)
                if code is None:
                    vals = self._values
                    code = len(vals)
                    vals.append(s)
                    self._index[s] = code
                    self.sorted = self.sorted and (
                        code < 1 or vals[code - 1] <= s)
        return code

    def encode(self, strings, add: bool = True) -> np.ndarray:
        return np.fromiter(
            (self.encode_one(s, add) for s in strings),
            dtype=np.int32,
            count=len(strings),
        )

    def decode_one(self, code: int) -> str:
        return self._values[code]

    def decode(self, codes: np.ndarray) -> list[str]:
        vals = self._values
        return [vals[c] if c >= 0 else None for c in codes]

    def values(self) -> list[str]:
        return list(self._values)

    @staticmethod
    def from_strings_bulk(strings: np.ndarray) -> tuple["Dictionary", np.ndarray]:
        """Vectorized build: unique+inverse in one numpy pass.

        Returns a SORTED dictionary (np.unique sorts) and int32 codes.
        ~100x faster than per-item encode for multi-million-row ingest.
        """
        values, codes = np.unique(np.asarray(strings), return_inverse=True)
        return Dictionary([str(v) for v in values], sorted_=True), codes.astype(
            np.int32
        )

    @staticmethod
    def merge(
        dl: "Dictionary | None", dr: "Dictionary | None"
    ) -> tuple["Dictionary | None", np.ndarray | None, np.ndarray | None]:
        """Common dictionary for combining two dict-encoded columns (set
        operations, cross-table comparisons). Returns (merged, remap_left,
        remap_right); a None remap means codes pass through unchanged."""
        if dr is None or dl is dr:
            return dl, None, None
        if dl is None:
            return dr, None, None
        lv, rv = dl.values(), dr.values()
        if lv == rv:
            return dl, None, None
        merged = Dictionary(sorted(set(lv) | set(rv)), sorted_=True)
        lmap = np.fromiter((merged._index[v] for v in lv), np.int32, len(lv))
        rmap = np.fromiter((merged._index[v] for v in rv), np.int32, len(rv))
        return merged, lmap, rmap

    def finalize_sorted(self, codes: np.ndarray) -> tuple["Dictionary", np.ndarray]:
        """Return an order-preserving dictionary and remapped codes, of
        one snapshot of this dictionary.

        After this, code order == lexicographic string order, enabling device
        range predicates and ORDER BY directly on codes.
        """
        values, remap = _sort_first(*self.snapshot())
        return Dictionary(values, sorted_=True), remap[codes]


def _sort_first(vals: list[str], n: int) -> tuple[list[str], np.ndarray]:
    """(the first `n` strings in string order, code -> place)."""
    vals = vals[:n]
    order = np.argsort(np.asarray(vals, dtype=object), kind="stable")
    remap = np.empty(n, dtype=np.int32)
    remap[order] = np.arange(n, dtype=np.int32)
    return [vals[i] for i in order], remap


CHUNK = 1024  # strings a chunk of a grown sorted view holds, up to twice


class Chunked:
    """A sorted sequence of strings whose versions share their chunks.

    Placing k strings copies the chunks they land in and the table of
    chunks, not the other strings: a view of a million strings grows by
    one in microseconds, where a copy increments a million reference
    counts. The first version is one chunk of any length; a chunk is split
    into CHUNK-string pieces when it passes 2 * CHUNK."""

    __slots__ = ("chunks", "starts")

    def __init__(self, chunks: list[list[str]]):
        self.chunks = chunks
        starts = [0]
        for c in chunks:
            starts.append(starts[-1] + len(c))
        self.starts = starts  # index of each chunk's first string, and n

    def __reduce__(self):
        return (Chunked, (self.chunks,))

    def __len__(self) -> int:
        return self.starts[-1]

    def __getitem__(self, i: int) -> str:
        c = bisect.bisect_right(self.starts, i) - 1
        return self.chunks[c][i - self.starts[c]]

    def __iter__(self):
        return itertools.chain.from_iterable(self.chunks)

    def bisect_left(self, s: str) -> int:
        chunks = self.chunks
        lo, hi = 0, len(chunks)
        while lo < hi:  # the first chunk whose last string is not below s
            mid = (lo + hi) // 2
            if chunks[mid][-1] < s:
                lo = mid + 1
            else:
                hi = mid
        if lo == len(chunks):
            return len(self)
        return self.starts[lo] + bisect.bisect_left(chunks[lo], s)

    def placed(self, strings: list[str]) -> tuple["Chunked", list[int]]:
        """This sequence with `strings` (sorted, none of them in it)
        placed, and the place each had in this one."""
        pos = [self.bisect_left(s) for s in strings]
        if not self.chunks:
            return Chunked([list(strings)]), pos
        last = len(self.chunks) - 1
        into: dict[int, list[tuple[int, str]]] = {}
        for s, p in zip(strings, pos):
            c = min(bisect.bisect_right(self.starts, p) - 1, last)
            into.setdefault(c, []).append((p - self.starts[c], s))
        out = []
        for c, chunk in enumerate(self.chunks):
            got = into.get(c)
            if got is None:
                out.append(chunk)
                continue
            chunk = list(chunk)
            for j, (at, s) in enumerate(got):
                chunk.insert(at + j, s)
            if len(chunk) > 2 * CHUNK:
                out.extend(chunk[i:i + CHUNK]
                           for i in range(0, len(chunk), CHUNK))
            else:
                out.append(chunk)
        return Chunked(out), pos


class SortedViews:
    """The sorted view of one append-order dictionary, kept as it grows.

    A view is (sorted Dictionary, remap): the dictionary's first `len(remap)`
    strings in string order, and each append-order code's place among them.
    The first view sorts; after that the k strings appended since the last
    view are placed by bisection (`Chunked`) and the remap is shifted:
    O(n) integer work and O(k log n) compares. A view is never changed once
    made: the tables and device batches built on it keep it."""

    __slots__ = ("source", "lineage", "view", "_lock")

    def __init__(self, source: Dictionary):
        self.source = source
        self.lineage = object()  # what the versions of this column share
        self.view: tuple[Dictionary, np.ndarray] | None = None
        self._lock = threading.Lock()

    def __reduce__(self):
        # a saved table comes back with its dictionary; views are made anew
        return (SortedViews, (self.source,))

    def current(self, n: int) -> tuple[Dictionary, np.ndarray] | None:
        """The newest view if it covers the first `n` codes."""
        v = self.view
        return v if v is not None and len(v[1]) >= n else None

    def extend_to(self, n: int) -> tuple[tuple[Dictionary, np.ndarray], str, int]:
        """(view covering the first `n` codes, "sort" / "insert" / "", the
        number of strings placed): one session builds, the others wait
        for it and take its view."""
        with self._lock:
            v = self.current(n)
            if v is not None:
                return v, "", 0
            vals, n = self.source.snapshot()
            if self.view is None:
                values, remap = _sort_first(vals, n)
                chunked = Chunked([values] if n else [])
                self.view = (Dictionary.view(chunked, self.lineage), remap)
                return self.view, "sort", n
            old, old_remap = self.view
            m = len(old_remap)
            new = sorted(range(m, n), key=vals.__getitem__)
            chunked, pos = old._values.placed([vals[c] for c in new])
            p = np.asarray(pos, dtype=np.int32)
            # an old string moves up by the new ones placed before it: a
            # compare a string while they are few, else a search
            if len(p) <= 8:
                shift = np.zeros(m, dtype=np.int32)
                for q in p:
                    shift += old_remap >= q
            else:
                shift = np.searchsorted(p, old_remap, side="right")
            remap = np.empty(n, dtype=np.int32)
            remap[:m] = old_remap + shift
            remap[np.asarray(new, dtype=np.int64)] = p + np.arange(
                len(p), dtype=np.int32)
            self.view = (Dictionary.view(chunked, self.lineage), remap)
            return self.view, "insert", len(p)


class DictPin:
    """A dictionary as one plan's compiled programs see it: the static
    metadata of a `ColumnBatch` handed to that plan's `jax.jit`.

    jit reuses a program when the static metadata compares equal. Two pins
    of one plan compare equal when they hold the same dictionary, or two
    versions of one lineage of which the plan's traces read nothing
    (`deps`, shared by the plan's pins and filled while it traces: any
    attribute read is "content", `domain(cap)` only that), so a string
    appended to the column does not trace the programs again unless they
    lowered something from the strings. A pin of a dictionary without a
    lineage compares by identity, as the dictionary itself does."""

    __slots__ = ("dictionary", "deps")

    def __init__(self, dictionary: Dictionary, deps: dict):
        self.dictionary = dictionary
        self.deps = deps

    def _key(self):
        lin = self.dictionary.lineage
        return lin if lin is not None else self.dictionary

    def __eq__(self, other) -> bool:
        if self is other:
            return True
        if type(other) is not DictPin or other.deps is not self.deps:
            return False
        a, b = self.dictionary, other.dictionary
        if a is b:
            return True
        lin = a.lineage
        if lin is None or lin is not b.lineage:
            return False
        if lin not in self.deps:
            return True
        cap = self.deps[lin]
        return cap is not None and a.domain(cap) == b.domain(cap)

    def __hash__(self) -> int:
        return hash(self._key())

    def _read(self, cap=None) -> Dictionary:
        lin = self.dictionary.lineage
        if lin is not None:
            have = self.deps.get(lin, cap)
            self.deps[lin] = cap if have == cap else None
        return self.dictionary

    def domain(self, cap: int) -> int:
        return self._read(cap).domain(cap)

    def __len__(self) -> int:
        return len(self._read())

    def __getattr__(self, name):
        return getattr(self._read(), name)
