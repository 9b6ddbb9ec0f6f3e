"""Traffic mixes: one parameter file per mix, one generator for all."""
