"""Global configuration for the crdt_tpu framework.

The reference library (`/root/reference/src/vclock.rs:23`) fixes
``Counter = u64``.  JAX needs ``jax_enable_x64`` for 64-bit integers, so we
enable it at import time (gate with ``CRDT_TPU_NO_X64=1`` to opt out, e.g.
for pure-f32 TPU perf experiments where counters fit in uint32).

The reference has no runtime configuration at all (no features, env vars or
flags — see SURVEY.md §5 "Config"); its only knobs are compile-time generics.
The TPU build replaces those generics with :class:`CrdtConfig`: capacities of
the dense SoA buffers (actor universe, member slots, deferred slots,
multi-value slots) and the counter dtype.
"""

from __future__ import annotations

import dataclasses
import os

_X64_ENABLED = False

# canonical ORSWOT pairwise-merge implementation names (the dispatch lives
# in crdt_tpu.ops.orswot_ops.resolve_merge_impl; configs accept "auto" too)
MERGE_IMPLS = ("rank", "unrolled", "pallas")


def enable_x64() -> bool:
    """Enable 64-bit types in JAX (idempotent). Returns True if enabled."""
    global _X64_ENABLED
    if os.environ.get("CRDT_TPU_NO_X64") == "1":
        return False
    if not _X64_ENABLED:
        import jax

        jax.config.update("jax_enable_x64", True)
        _X64_ENABLED = True
    return _X64_ENABLED


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at its one home and
    return the directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself
    and nothing is set here.  Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored).  The path is part of the
    cache key, so it is fixed — never derived from a temp name, a pid or
    the time.  Set through ``jax.config`` so it takes effect even after
    jax has been imported (``enable_x64`` imports it)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def x64_disabled():
    """Context manager forcing 32-bit trace mode for a kernel trace
    (Mosaic has no 64-bit support — Python-int literals must not become
    i64[] operands)."""
    import jax

    return jax.enable_x64(False)


def counter_dtype(config=None):
    """The dtype used for dense counters.

    The reference fixes ``Counter = u64`` (`vclock.rs:23`) and that is
    the default.  TPUs have no native 64-bit integers — XLA emulates
    them as register pairs, roughly doubling both arithmetic and HBM
    traffic — so :class:`CrdtConfig` can opt a batch universe into
    ``counter_bits=32`` where counters are known to fit (2^32 ops per
    actor); the scalar/u64 engines remain the parity oracle.
    """
    return dtype_for_bits(config.counter_bits if config is not None else 64)


def dtype_for_bits(bits: int):
    """Counter dtype for an explicit width (kernel dataclasses carry the
    width as a plain int so they stay hashable/static under jit)."""
    import jax.numpy as jnp

    if bits == 32:
        return jnp.uint32
    return jnp.uint64 if enable_x64() else jnp.uint32


@dataclasses.dataclass(frozen=True)
class CrdtConfig:
    """Static capacities for dense SoA CRDT batches.

    The reference stores unbounded BTreeMaps/HashMaps; XLA requires static
    shapes, so each axis gets a capacity.  Overflow policy: raising on the
    host at ingest time (capacities are checked when ops/states are packed,
    never on device).
    """

    num_actors: int = 64  # actor-universe size A (dense interned ids)
    member_capacity: int = 32  # Orswot member slots per object
    deferred_capacity: int = 8  # deferred (clock, member) rows per object
    mv_capacity: int = 8  # MVReg antichain slots per register
    key_capacity: int = 16  # Map key slots per object
    # counter width: 64 = reference parity (u64, vclock.rs:23), 32 = the
    # TPU-native width (no 64-bit emulation; counters must fit 2^32)
    counter_bits: int = 64
    # ORSWOT pairwise-merge implementation: "auto" (env override via
    # CRDT_MERGE_IMPL, else backend default), "rank", "unrolled", or
    # "pallas" — see crdt_tpu.ops.orswot_ops.resolve_merge_impl
    merge_impl: str = "auto"

    def __post_init__(self):
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name == "merge_impl":
                if v != "auto" and v not in MERGE_IMPLS:
                    raise ValueError(
                        f"CrdtConfig.merge_impl must be 'auto' or one of "
                        f"{'/'.join(MERGE_IMPLS)}, got {v!r}"
                    )
                continue
            if not isinstance(v, int) or v <= 0:
                raise ValueError(f"CrdtConfig.{f.name} must be a positive int, got {v!r}")
        if self.counter_bits not in (32, 64):
            raise ValueError(
                f"CrdtConfig.counter_bits must be 32 or 64, got {self.counter_bits!r}"
            )

    @classmethod
    def tpu_default(cls, **overrides) -> "CrdtConfig":
        """The recommended production config for TPU workloads.

        ``counter_bits=32``: the measured product default (the unrolled
        and fused-Pallas fast paths are exact for uint32 only, and u64
        measured 1.5× the u32 cost even on CPU — `docs/GUIDE.md` "Counter
        width").  The u64 default on :class:`CrdtConfig` itself stays for
        reference parity (`vclock.rs:23`); use this constructor when the
        per-actor op count fits 2^32."""
        return cls(**{"counter_bits": 32, **overrides})


DEFAULT_CONFIG = CrdtConfig()
