"""Actor/member interning — host-side registries for dense device buffers.

The reference allows any ``Ord + Hash`` actor (`/root/reference/src/vclock.rs:27-28`)
and any hashable member (`orswot.rs:19-20`); XLA wants dense integer axes.
Interning maps arbitrary Python values to stable dense indices losslessly
(SURVEY.md §7.0): actors → ``[0, A)`` columns of the actor axis, members →
int32 ids (with ``-1`` reserved for empty slots).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Hashable, Iterable, List


class Registry:
    """A bidirectional value ↔ dense-index map, append-only.

    A registry whose values are all ``str`` or ``bytes`` mirrors itself
    into a native name table (:meth:`native_names`) that the named wire
    codec (`crdt_tpu/native/wire_ingest.cpp`) reads and extends: a
    native parse interns the names it meets there, and
    :meth:`adopt_native` appends them here, so this registry stays the
    one truth (``lookup(id)`` returns the name).  Values are appended
    under ``lock``; hold it across a native call that may intern, so a
    parse on one thread can intern while another thread encodes."""

    __slots__ = ("_to_idx", "_to_val", "capacity", "lock", "_names",
                 "_named")

    def __init__(self, capacity: int | None = None):
        self._to_idx: Dict[Hashable, int] = {}
        self._to_val: List[Hashable] = []
        self.capacity = capacity
        self.lock = threading.RLock()
        self._names = None  # the native name table, built on first use
        self._named = True  # every value so far is a str or bytes

    def __len__(self) -> int:
        return len(self._to_val)

    def __contains__(self, value: Hashable) -> bool:
        return value in self._to_idx

    def intern(self, value: Hashable) -> int:
        idx = self._to_idx.get(value)
        if idx is not None:
            return idx
        with self.lock:
            idx = self._to_idx.get(value)
            if idx is not None:
                return idx
            idx = len(self._to_val)
            if self.capacity is not None and idx >= self.capacity:
                raise ValueError(
                    f"registry capacity {self.capacity} exhausted interning "
                    f"{value!r}"
                )
            if type(value) is not str and type(value) is not bytes:
                self._named = False
            # the list first: a lock-free reader that finds the index in
            # the dict can always look it up
            self._to_val.append(value)
            self._to_idx[value] = idx
            return idx

    def intern_all(self, values: Iterable[Hashable]) -> List[int]:
        return [self.intern(v) for v in values]

    def lookup(self, idx: int) -> Any:
        return self._to_val[idx]

    def values(self) -> List[Hashable]:
        return list(self._to_val)

    # -- the native name table -------------------------------------------

    def native_names(self):
        """The native name table holding every name interned so far (its
        ids are this registry's), or None when a value is not a ``str``
        or ``bytes`` (or not UTF-8 encodable).  Raises what the native
        loader raises when the library is unavailable."""
        if not self._named:
            return None
        names = self._names
        if names is not None and len(names) >= len(self._to_val):
            return names
        with self.lock:
            if self._names is None:
                from ..native.engine import NameTable

                self._names = NameTable(
                    (1 << 31) - 1 if self.capacity is None else self.capacity)
            have = len(self._names)
            if have < len(self._to_val):
                from .serde import to_binary

                try:
                    encoded = [to_binary(v) for v in self._to_val[have:]]
                except UnicodeEncodeError:
                    self._named = False
                    return None
                self._names.append(encoded)
            return self._names

    def native_backlog(self) -> int:
        """Names the native table interned that this registry has not
        adopted yet."""
        return 0 if self._names is None else \
            len(self._names) - len(self._to_val)

    def adopt_native(self) -> int:
        """Append the names the native table interned beyond this
        registry, in its order; returns how many."""
        from .serde import from_binary

        with self.lock:
            have, new = len(self._to_val), self.native_backlog()
            if new <= 0:
                return 0
            for raw in self._names.read(have, have + new):
                value = from_binary(raw)
                self._to_val.append(value)
                self._to_idx[value] = len(self._to_val) - 1
            return new


class IdentityRegistry:
    """A registry whose dense index IS the value — non-negative ints only.

    The bulk wire-ingest paths (:meth:`OrswotBatch.from_wire` and the
    other types' ``from_wire`` → the native parallel decoders,
    `crdt_tpu/native/wire_ingest.cpp`) decode million-object fleets
    without touching any Python per-value state; with integer keys that
    makes interning a no-op.  For integer actors (< the
    actor-axis capacity) and integer members (int32 range) the identity
    map is lossless: ``lookup`` returns the original int, so
    ``value_sets``/``to_scalar`` work unchanged."""

    __slots__ = ("capacity",)

    #: duck-typing marker the bulk paths dispatch on
    identity = True

    def __init__(self, capacity: int | None = None):
        self.capacity = capacity

    def __len__(self) -> int:
        # every index in range is permanently "interned"; the int32 id
        # space [0, 2^31) stands in for the unbounded member registry
        # (2^31 - 1 itself is a valid id — the native decoder accepts it)
        return self.capacity if self.capacity is not None else (1 << 31)

    def __contains__(self, value: Hashable) -> bool:
        return (
            isinstance(value, int) and not isinstance(value, bool)
            and 0 <= value < len(self)
        )

    def intern(self, value: Hashable) -> int:
        if value not in self:
            raise ValueError(
                f"identity registry holds non-negative ints < {len(self)}; "
                f"got {value!r} (use a standard Universe for arbitrary "
                "hashable values)"
            )
        return value

    def intern_all(self, values: Iterable[Hashable]) -> List[int]:
        return [self.intern(v) for v in values]

    def lookup(self, idx: int) -> Any:
        return int(idx)

    def values(self) -> List[Hashable]:
        # identity registries carry no per-value state; checkpoints record
        # the identity marker instead of a value list (utils/checkpoint)
        return []


class Universe:
    """The interning context shared by a family of batch CRDTs.

    Holds the actor registry (dense columns of the actor axis) and the
    member registry (Orswot member ids / MVReg payload ids), plus the static
    capacities (:class:`crdt_tpu.config.CrdtConfig`).

    :meth:`identity` builds a universe whose registries are identity maps
    over non-negative ints — zero host-side interning state, required by
    the native bulk wire legs of every type but ORSWOT and recommended
    whenever actors and members are already dense integers.  ORSWOT's
    native legs also take universes whose registries hold only ``str`` /
    ``bytes`` names (:meth:`Registry.native_names`).
    """

    def __init__(self, config=None, *, actors=None, members=None):
        from ..config import DEFAULT_CONFIG

        self.config = config or DEFAULT_CONFIG
        self.actors = actors if actors is not None else Registry(
            capacity=self.config.num_actors
        )
        self.members = members if members is not None else Registry()

    @classmethod
    def identity(cls, config=None) -> "Universe":
        """A universe with identity interning (int actors < num_actors,
        int32 members) — the zero-overhead mode the bulk wire-ingest
        fast path requires."""
        from ..config import DEFAULT_CONFIG

        cfg = config or DEFAULT_CONFIG
        return cls(
            cfg,
            actors=IdentityRegistry(capacity=cfg.num_actors),
            members=IdentityRegistry(),
        )

    @property
    def is_identity(self) -> bool:
        return (
            getattr(self.actors, "identity", False)
            and getattr(self.members, "identity", False)
        )

    def actor_idx(self, actor) -> int:
        return self.actors.intern(actor)

    def member_id(self, member) -> int:
        return self.members.intern(member)
