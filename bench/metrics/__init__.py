"""One reader per per-layer metric, found by the metric's name.

A reader declares ``LAYER``, ``UNIT``, ``MOVES`` (the one end-to-end metric
it should move) and ``SOURCE``, and ``read(run)`` returns the number, or
``None`` where the run holds nothing for it to read (the harness then
leaves the metric out).  ``run`` carries ``trace`` (the reduced device
trace of the measured window, or ``None``), ``facts`` (what the driver
counted), ``peaks`` (the device's row of ``bench/peaks.json``) and
``chips``.
"""
