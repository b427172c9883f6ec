"""Per-layer metrics, one reader per file, found by the metric's name:
``bench/metrics/<name>.py`` defines ``read(run) -> float | None``.

``run`` is the ``bench.run.Measured`` record of the traced window:
``window_s`` and ``window_host`` (host clock), ``rows``, ``chips``,
``peaks``, ``macs_per_image``, ``eval_images``, ``lut_calls`` (the
``(operations, bytes)`` of every LUT matmul launch of the completed
banks), ``host`` (``bench.monitor.Monitor.between`` of the window) and
``trace`` (``bench.trace.Reduced``, or None).  A reader that finds
nothing to read returns None, and the metric is left out of the line.
"""
