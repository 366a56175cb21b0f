"""The yardstick: peaks, counts, weights, traffic, the plain reference, the
trace reduction and the result line. Nothing here imports the program."""
