"""The benchmark's own library: traffic, weights, reference, arithmetic,
trace reduction and the run of one cell. It imports nothing of the program
except in ``harness`` (the system under test) and ``weights.to_program``
(the layout the program takes its parameters in)."""
