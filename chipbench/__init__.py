"""The on-chip benchmark of sparkdl-tpu: one cell a process, driven by
the data files beside this one (see README.md). The yardstick lives
here and takes from the program only the system under test."""
