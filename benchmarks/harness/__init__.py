"""The benchmark's own yardstick: manifest loading, the peaks table,
operations-and-bytes functions, the trace reduction, the traffic
generator and the comparison that decides ``correct``. Nothing here
imports the program."""
