"""Plain references: the same semantics as the system under test, written
independently of it (they import nothing of the program)."""
