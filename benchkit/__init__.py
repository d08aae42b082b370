"""The benchmark harness's load-bearing machinery, split out of the
``bench.py`` runner (VERDICT r4 item 8) so each piece is testable on its
own while ``python bench.py`` keeps the artifact contract:

* :mod:`benchkit.core` — JSON-line state + incremental ``emit``, the
  wall-clock budget, per-stage isolation (``run_stage``; a failed stage
  makes the run exit non-zero), CPU downshift, and the chained timing
  helpers.
* :mod:`benchkit.artifacts` — round-over-round regression diffing.
"""
