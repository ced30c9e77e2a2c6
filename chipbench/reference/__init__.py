"""Plain references: what the timed path's answers are compared with."""
