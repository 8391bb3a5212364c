"""The yardstick: traffic, references, work arithmetic, peaks and the
trace reduction. Nothing here imports the program under test except the
two drivers, which call its entry points."""
