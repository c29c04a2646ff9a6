"""Plain numpy references of the cells' semantics.  They import nothing of
the program and take nothing it made: only the inputs the benchmark
generated and the answers the program returned."""
