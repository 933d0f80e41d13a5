"""The benchmark's own code: traffic, weights, the plain references, the
trace reduction, operation counts and the peaks table. Nothing here is
imported by the program, and the references import nothing of it."""
