"""The benchmark harness: finding a cell's files, the program under test,
the plain reference, the comparison, the traffic and the trace."""
