"""The on-chip benchmark of the replay kernel and the event engines."""
