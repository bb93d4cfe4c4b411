"""The benchmark's own yardstick: data from the seed, work counts, peaks,
trace reduction, load generation and the comparison that decides `correct`.
Nothing here imports the program except the two job kinds under `jobs/`."""
