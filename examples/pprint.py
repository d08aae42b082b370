"""Pretty-print demo — the counterpart of the reference's only example
(`/root/reference/examples/pprint.rs:1-21`): build a VClock and a
multi-value register, show their Display forms, then do the same for a
batched ORSWOT fleet via the host-side pretty-printer.

Run:  PYTHONPATH=. python examples/pprint.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from crdt_tpu import MVReg, VClock


def main():
    # VClock Display — `(actor->count, ...)` (`vclock.rs:73-84`)
    vclock = VClock()
    vclock.witness(31231, 2)
    vclock.witness(4829, 9)
    vclock.witness(87132, 32)
    print(f"vclock:\t{vclock}")

    # MVReg Display — `|val@(clock), ...|` (`mvreg.rs:61-72`); two
    # concurrent writers leave both values visible
    reg = MVReg()
    op1 = reg.set("some val", reg.read().derive_add_ctx(9742820))
    op2 = reg.set("some other val", reg.read().derive_add_ctx(648572))
    reg.apply(op1)
    reg.apply(op2)
    print(f"reg:\t{reg}")

    # batch-engine parity: pack a small ORSWOT fleet onto the device path
    # and pretty-print each object from the SoA buffers (host-side Display,
    # SURVEY.md §5 "tracing")
    from crdt_tpu import Orswot
    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.utils.interning import Universe

    uni = Universe(CrdtConfig(num_actors=4, member_capacity=8, deferred_capacity=4))
    fleet = []
    for items in (["apple", "pear"], ["plum"]):
        s = Orswot()
        for actor, member in enumerate(items):
            s.apply(s.add(member, s.value().derive_add_ctx(actor)))
        fleet.append(s)
    batch = OrswotBatch.from_scalar(fleet, uni)
    for i, scalar in enumerate(batch.to_scalar(uni)):
        print(f"orswot[{i}]:\t{{{', '.join(sorted(map(str, scalar.value().val)))}}}")


if __name__ == "__main__":
    main()
