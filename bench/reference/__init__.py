"""Plain reference of the simulated semantics (imports nothing of the program)."""
