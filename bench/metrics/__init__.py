"""Per-layer metrics, one reader per file, found by the metric's name:
``bench/metrics/<name>.py`` defines ``read(run) -> float | None``.

``run`` is the ``bench.run.Measured`` record of the traced window:
``window_s`` and ``window_host`` (host clock), ``rows``, ``chips``,
``peaks``, ``macs_per_item`` and ``eval_items`` (the MACs of one eval
item and the items of a row, ``bench/counts/<family>.py``), ``lut_calls``
(the ``(operations, bytes)`` of every LUT matmul launch of the completed
banks, on every chip), ``host`` (``bench.monitor.Monitor.between`` of
the window) and ``trace`` (``bench.trace.Reduced``, or None).  A reader that finds
nothing to read returns None, and the metric is left out of the line.
"""
