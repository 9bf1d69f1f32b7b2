"""Plain reference of the benchmarked federated rounds.

Imports nothing of the program and takes nothing it made: the weights, the
cohorts and the tokens are made again here from the seed, and the rounds
are computed in float32 with matmuls at ``highest`` precision.
"""
