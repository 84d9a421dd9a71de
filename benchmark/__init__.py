"""The benchmark of swiftly-tpu: one harness (`harness`), one traffic
generator (`drive`), the plain reference (`reference`), the comparison
that decides ``correct`` (`check`), the stage counts (`counts`), the
trace reduction (`trace`, `reading`) and the files found by name under
``configs/``, ``traffic/``, ``metrics/`` and ``limits/``."""
