"""One file a driver: the closed loop of one kind of cell.  A driver builds
the program's state from the generated inputs, warms up every shape, runs
one step at a time (each ending in a host read), and after the window
frees the program's state and compares what the timed path produced with
the plain reference.  The harness finds a driver by the ``driver`` name in
the cell's traffic file."""
