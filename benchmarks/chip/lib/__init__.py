"""The chip benchmark's own code: server child, load generator,
percentiles, trace reduction, FLOPs from shapes, checks.  Nothing here
imports the program under test; ``xplane.py`` alone imports JAX, and only
to read a trace file after the server child has exited."""
