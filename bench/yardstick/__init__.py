"""The benchmark's yardstick: what later changes to the program cannot move.

Traffic generation (``fbtrace``), the plain referee and the comparison that
decides ``correct`` (``check``), percentiles (``stats``), host spans and
compile counts (``instrument``, ``layers``), the reduction of profiler
traces to device metrics (``devtrace``), the kernel's work count
(``work``), the table of device peaks (``peaks``) and the lookup of
configurations, mixes, drivers and metric readers by name (``registry``).
"""
