"""Plain float32 references the benchmark compares the program with."""
