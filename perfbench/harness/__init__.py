"""The benchmark's own code: traffic generation, trace reduction, peaks,
work counts and the comparison that decides `correct`. Nothing here is
imported by the program under test."""
