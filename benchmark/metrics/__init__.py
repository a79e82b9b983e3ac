"""Per-layer metric readers, one module per metric of BENCHMARK.json's
``per_layer``, each with ``read(ctx) -> float | None``.  ``ctx`` holds the
reduced trace (``trace``), the traced window (``w0``, ``w1``), the
device's busy seconds in it (``busy_s``), the circuits completed in it
(``circuits``) and the device's peaks (``peaks``).  A reader that finds
nothing to read returns None and the metric is left out."""
