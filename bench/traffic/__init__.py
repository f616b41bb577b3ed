"""Traffic mixes (data files here) and the seeded generator and loops
that turn a mix into requests sent to the engine."""
