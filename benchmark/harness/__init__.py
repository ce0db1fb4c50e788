"""The yardstick: arithmetic, references, costs, peaks and the trace reduction
that later PRs may not change. Nothing here imports the program."""
