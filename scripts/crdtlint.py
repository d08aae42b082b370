#!/usr/bin/env python
"""crdtlint entry point — identical to ``python -m crdt_tpu.analysis``.

Both tiers: the default stdlib-only AST lint, and ``--kernels`` for the
jaxpr tier (kernelcheck, KC01-KC05 — imports jax under
``JAX_PLATFORMS=cpu``; see docs/GUIDE.md "Kernel contracts").

Kept as a script so CI configs and editors can point at a file; all
logic lives in :mod:`crdt_tpu.analysis.__main__`.  Works from any CWD:
the repo root is derived from this file's location, not the caller's.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from crdt_tpu.analysis.__main__ import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
