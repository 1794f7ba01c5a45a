"""One driver per kind of system under test; a configuration's file names
its driver. A driver builds the program from the configuration, warms it up,
runs the window, and compares what the window's own path produced."""
