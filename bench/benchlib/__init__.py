"""The benchmark's yardstick: discovery, data, traffic arrivals, the plain
reference, the comparisons that decide ``correct``, the trace reduction,
the peaks table and the work counts.  Nothing here imports the program
(``repro``) except where a function's docstring says it drives it."""
