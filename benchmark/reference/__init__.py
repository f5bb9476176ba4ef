"""Plain references the benchmark compares the program with.  Nothing here
imports the program."""
