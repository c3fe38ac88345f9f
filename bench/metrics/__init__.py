"""One reader per per-layer metric of BENCHMARK.json.  ``read(ctx)`` returns
the metric or None when the trace holds nothing to read.  ``ctx`` holds the
reduced trace (``trace``), the traced window in seconds (``window_s``), the
episodes it covers (``episodes``), the cell's ``chips``, the compiles
counted in the window (``compiles_in_window``), the episode's counted
``work`` and the device's ``peaks``."""
