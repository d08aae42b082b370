"""Op-based write front-end — batched ``CmRDT::apply`` (L0/L2).

The reference crate defines TWO replication models (`/root/reference/
src/traits.rs`): state-based ``CvRDT::merge`` — everything this repo
shipped before this package (wire codec, digest/delta sync, ARQ
transport, gossip fleet) — and op-based ``CmRDT::apply`` with causal
contexts (`ctx.rs`).  This package is the op model at batch scale, the
heavy-traffic ingest path: a million users generate small ops, not
2 GB state blobs.

* :mod:`~crdt_tpu.oplog.records` — columnar :class:`OpBatch` /
  bounded :class:`OpLog`, and the batched :func:`derive_add_ctx` /
  :func:`derive_rm_ctx` causal-context kernels.
* :mod:`~crdt_tpu.oplog.apply` — :class:`OpApplier`: jit-able
  scatter-fold of op batches into the ORSWOT dense planes (duplicate
  dots idempotent, causal gaps parked), plus the counter/LWW scatter
  folds.
* :mod:`~crdt_tpu.oplog.wire` — the versioned+CRC op-frame codec
  (``Op::Add`` ships a 23-byte row, not a state blob).

Integration: :class:`crdt_tpu.cluster.ClusterNode.submit_ops` ingests
live writes between anti-entropy rounds, sync sessions piggyback
pending op batches exactly like fleet snapshots (PR 6), and
:class:`crdt_tpu.batch.wireloop.PipelinedOpLoop` overlaps frame decode
with the fold.  docs/GUIDE.md "Op-based replication" documents the frame
format and the ship-ops-vs-ship-deltas tradeoff.
"""

from .apply import (  # noqa: F401
    ApplyReport,
    OpApplier,
    apply_gcounter_ops,
    apply_lww_ops,
    apply_pncounter_ops,
)
from .records import (  # noqa: F401
    NO_MEMBER,
    OP_ADD,
    OP_DEC,
    OP_INC,
    OP_KINDS,
    OP_RM,
    OP_SET,
    OpBatch,
    OpLog,
    derive_add_ctx,
    derive_rm_ctx,
    intern_ops,
)
from .wire import (  # noqa: F401
    FRAME_OPS,
    OPLOG_PROTOCOL_VERSION,
    decode_ops_frame,
    encode_ops_frame,
    frame_bytes_per_op,
)

__all__ = [
    "ApplyReport",
    "FRAME_OPS",
    "NO_MEMBER",
    "OPLOG_PROTOCOL_VERSION",
    "OP_ADD",
    "OP_DEC",
    "OP_INC",
    "OP_KINDS",
    "OP_RM",
    "OP_SET",
    "OpApplier",
    "OpBatch",
    "OpLog",
    "apply_gcounter_ops",
    "apply_lww_ops",
    "apply_pncounter_ops",
    "decode_ops_frame",
    "derive_add_ctx",
    "derive_rm_ctx",
    "encode_ops_frame",
    "frame_bytes_per_op",
    "intern_ops",
]
