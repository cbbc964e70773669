"""The on-chip benchmark's own code: cell resolution, the general job
generators (``kinds``), seeded weights and data, the plain references,
FLOP and byte counts from shapes, the peak table and the trace reader.
Nothing here is imported by the program under test."""
