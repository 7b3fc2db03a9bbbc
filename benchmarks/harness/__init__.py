"""The benchmark's own yardstick: traffic, work and peaks, the trace
reduction, the plain references and the comparison that decides ``correct``.
Nothing here is imported by the program, and only the two drivers import it."""
