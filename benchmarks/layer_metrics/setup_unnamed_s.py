"""run["setup_s"] less the six parts the program's start-up record names:
every import, the harness's task, weights and traffic list, the warm-up after
the first step or loop, the machine. Reported, not judged. Prints the notes
startup_tiling and startup."""
from benchmarks.harness import startup

Read = startup.Unnamed
